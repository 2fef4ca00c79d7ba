"""Engine and steps, backlog cells: 95th percentile of the gap between
consecutive tokens of one request (client clock, ms) over every gap whose
later token lies inside the window. Recorded, not judged: it sits between
plain decode cycles and cycles that carry a prompt chunk."""
from benchmark.lib import serve
from benchmark.lib import stats as S


def read(r):
    if r.get("mode") != "backlog":
        return None
    _, gaps = serve.window_token_times(r["records"], r["t0"], r["t1"])
    return S.percentile(gaps, 95) if gaps else None
