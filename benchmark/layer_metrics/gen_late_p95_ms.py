"""Load generator: 95th percentile of (sent - due), ms, over the
requests due inside the window. A starved generator is then not read as
a fast server."""
from benchmark.lib import stats as S


def read(r):
    late = r.get("late_ms")
    return S.percentile(late, 95) if late else None
