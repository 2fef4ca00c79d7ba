"""Every device op of a launch says which launch and which section of the
model (ISSUE 37): the step programs put their ops under the spec's
SECTIONS and are named for what they are, a launch's flight-recorder
record says which program it ran, and the profiler's trace reader
(``profiler/xplane.py``) reads the names back from a trace recorded on a
v5e (``benchmark/tests/data/small.xplane.pb``)."""
import os
import re
import shutil

import numpy as np
import pytest

from paddle_tpu.models import decoder_spec as DS
from paddle_tpu.profiler import xplane
from paddle_tpu.serving import GenerationEngine

import _toys

SMALL = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "tests", "data", "small.xplane.pb")

ALWAYS = {DS.EMBED, DS.NORM, DS.QKV, DS.CACHE_WRITE, DS.ATTENTION,
          DS.O_PROJ, DS.MLP}
ONE_TOKEN = {DS.HEAD, DS.SAMPLE}
ROUTED = {DS.MOE_SCOPE, DS.ROUTER}
MIXER = {DS.SSM_PROJ, DS.SSM_CONV, DS.SSM_SCAN}


# family (a name of ``_toys.default``): (the vocabulary it uses, its
# step's name)
FAMILIES = {
    "gpt2": (ALWAYS | ONE_TOKEN, "fused_step"),
    "axk1": (ALWAYS | ONE_TOKEN | ROUTED | {DS.SHARED_EXPERT},
             "fused_step"),
    "sdar": (ALWAYS | ROUTED | {DS.UNMASK_SCOPE}, "block_step"),
    "mimo": (ALWAYS | ONE_TOKEN | ROUTED, "fused_step"),
    "falcon_h1": (ALWAYS | ONE_TOKEN | MIXER, "fused_step"),
    # every block is half a layer: the E blocks' add is the ``mlp`` word
    "nemotron_h": (ALWAYS | ONE_TOKEN | MIXER | ROUTED
                   | {DS.SHARED_EXPERT, DS.LATENT_PROJ}, "fused_step"),
}


def test_section_of_takes_the_innermost_word():
    path = "jit(fused_step_q512_t64)/layer3/moe_experts/router/dot_general"
    assert DS.section_of(path) == DS.ROUTER
    assert DS.section_of("jit(f)/layer3/moe_experts/while/body/add") \
        == DS.MOE_SCOPE
    assert DS.section_of("jit(f)/layer3/add") is None
    assert DS.MOE_SCOPE == "moe_experts" and DS.UNMASK_SCOPE == "unmask"
    assert len(set(DS.SECTIONS)) == len(DS.SECTIONS) == 19
    with pytest.raises(ValueError, match="no section"):
        DS.section("attn")


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_op_of_a_step_lies_under_a_section(family):
    """Lower the family's step program at tiny size: its module is named
    for the step and its buckets, every vocabulary word the family uses
    is in the lowered text's op names, no op of the step lies under none
    (the kernels' interpreted bodies are under ``cache_write`` and
    ``attention``), every layer's ops under its ``layer{i}``; and the
    record of a launch the engine ran names the same program."""
    words, step = FAMILIES[family]
    eng = GenerationEngine(_toys.default(family), num_slots=2, max_len=64, block_size=8)
    try:
        Q, T = 8, 1
        name = f"{step}_q{Q}_t{T}"
        site = eng._fused_step_fn(Q, T)
        assert site.jitted.__name__ == name
        text = site.jitted.lower(
            eng._params, eng._buffers, eng._pool_operand(),
            *eng._null_step_operands(Q, T)).as_text(debug_info=True)
        assert re.search(r"module @(\S+)", text).group(1) == f"jit_{name}"
        # an op's name is its scope path from the jitted function down;
        # the other names in the text are callees' own, path-less
        ops = {n for n in re.findall(r'loc\("([^"]+)"', text)
               if n.startswith(f"jit({name})/")}
        assert len(ops) > 50
        assert {DS.section_of(n) for n in ops} == words, \
            sorted(n for n in ops if DS.section_of(n) is None)
        # the profiler's reader knows no vocabulary: its innermost scope
        # of the program's own function is the same word
        # (a trace's ``tf_op`` ends in the op; some of these in a scope)
        assert {n for n in ops if DS.section_of(n) != xplane.section_of(
            n + "/op" * (n.rsplit("/", 1)[-1] in DS.SECTIONS))} == set()
        layers = len(eng._decoder_spec.layers)
        tower = {DS.QKV, DS.CACHE_WRITE, DS.ATTENTION, DS.O_PROJ}
        for n in ops:
            if DS.section_of(n) in tower:
                assert re.match(rf"jit\({name}\)/layer\d+/", n), n
        assert {int(i) for n in ops
                for i in re.findall(r"/layer(\d+)/", n)} \
            == set(range(layers))
        # the expert layer is still found whole by its old needle
        if DS.ROUTER in words:
            assert all("/moe_experts/" in n for n in ops
                       if DS.section_of(n) in (DS.ROUTER, DS.SHARED_EXPERT))
        eng.submit(np.arange(3, 8, dtype=np.int32),
                   max_new_tokens=4).result(timeout=300)
    finally:
        eng.close()          # joins the scheduler: the last record is in
    launched = [c for c in eng.flight_recorder.snapshot()["cycles"]
                if c.get("launch_q")]
    assert launched
    for c in launched:
        assert c["launch_program"] \
            == f"{step}_q{c['launch_q']}_t{c['launch_t']}"
        assert c["launch_program"] \
            == eng._fused_step_fn(c["launch_q"],
                                  c["launch_t"]).jitted.__name__


def test_the_trace_reader_reads_scopes_sources_and_run_ids(tmp_path):
    """``small.xplane.pb`` (three ``small_step`` calls on a v5e): the
    fusion's metadata says which scope and which source line made it,
    the three module events and the three ``DoEnqueueProgram`` events
    carry ``run_id`` 4, 5, 6, and the by-section table puts the fusion's
    three runs of 12.6 us under ``small_step_body``."""
    meta = xplane.op_metadata(SMALL)
    assert "/device:TPU:0" in meta
    fusion, = [m for m in meta["/device:TPU:0"]
               if m["name"].startswith("%fusion")]
    assert "small_step_body" in fusion["tf_op"]
    assert fusion["tf_op"].startswith("jit(small_step)/")
    assert fusion["source"].endswith("benchmark/tools/record_trace.py:22")
    assert fusion["flops"] == 2 * 1024 ** 3 + 4 * 1024 ** 2
    assert fusion["bytes_accessed"] == 3 * 2 * 1024 ** 2
    assert xplane.section_of(fusion["tf_op"]) == "small_step_body"
    assert xplane.section_of("jit(f)/while/body/add:") == xplane.NO_SECTION
    assert xplane.section_of(
        "jit(fused_step_q8_t1)/layer0/moe_experts/router/top_k:") \
        == DS.ROUTER

    shutil.copy(SMALL, tmp_path)
    modules = xplane.module_events(str(tmp_path))
    assert [m["run_id"] for m in modules] == [4, 5, 6]
    assert {m["module"] for m in modules} == {"jit_small_step"}
    assert all(m["program_id"] == fusion["program_id"] for m in modules)
    # each was enqueued by the host event with its run_id, in order
    enqueued = [m["enqueue_us"] for m in modules]
    assert None not in enqueued and enqueued == sorted(enqueued)
    with open(SMALL, "rb") as f:
        planes = xplane.parse_xspace(f.read())
    assert sorted(xplane.enqueue_events(planes)) == [4, 5, 6]

    rows = xplane.device_section_table(str(tmp_path))
    body, = [r for r in rows if r["section"] == "small_step_body"]
    assert body["module"] == "jit_small_step" and body["calls"] == 3
    assert body["total_us"] == pytest.approx(3 * 12.6, rel=0.01)
    # own times: the sections' shares are the whole of the busy time
    assert sum(r["share"] for r in rows) == pytest.approx(1.0)
    ops = xplane.device_op_table(str(tmp_path))
    by_name = {r["name"].split(" = ")[0]: r for r in ops}
    assert by_name["%fusion"]["section"] == "small_step_body"
    assert by_name["%fusion"]["source"] == fusion["source"]
    assert by_name["%copy-done"]["section"] is None


def test_profiler_summary_prints_time_by_section_and_module(tmp_path):
    from paddle_tpu import profiler as prof_mod
    shutil.copy(SMALL, tmp_path)
    prof = prof_mod.Profiler(
        targets=[prof_mod.ProfilerTarget.CPU, prof_mod.ProfilerTarget.TPU],
        trace_dir=str(tmp_path))
    text = prof.summary()
    assert "Device ops (from XPlane)" in text
    launches, section = [ln for ln in text.splitlines()
                         if ln.startswith("jit_small_step ")][:2]
    assert launches.split()[1] == "3"                  # three launches
    assert section.split()[:3] == ["jit_small_step", "small_step_body", "3"]
    assert section.split()[-1] == "85.6%"
