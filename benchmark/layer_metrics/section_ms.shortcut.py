"""Engine and steps: own device ms a launch under the add that closes a
routed shortcut (``shortcut``: the experts' sum carried from the opening
sub-block joins the stream at the end of the closing one), all layers,
over the slice's launches matched by ``run_id``
(``lib/launch_trace.py``).

On one chip XLA fuses that add into the closing sub-block's down
projection (the fusion carries its ROOT's section, ``mlp``), so no trace
event is the section's and its own time is 0: where the compiled steps
hold instructions under the scope (``readings["scope_keys"]``,
``lib/scope_ops.py``) and the trace's sections were read, that is what is
returned — the add costs no pass of its own. It reads above 0 once
something stands between the two (an exchange between expert shares whose
result the add waits for). ``None`` where the program names no such
section."""
from benchmark.lib import launch_trace as LT


def read(r):
    ms = LT.section_ms(r, "shortcut")
    if ms is None and r.get("scope_keys", {}).get("shortcut") \
            and LT.section_ms(r, "mlp") is not None:
        return 0.0
    return ms
