"""The period scale of a cell: the shortest at which the program's
admission analysis admits every stream of the mix at the cell's declared
costs (so 10% shorter it refuses some).  Pure analysis, on the CPU:

    JAX_PLATFORMS=cpu python3 bench/period_scale.py <cell>

Writes nothing; the scale it prints goes into ``bench/cells/<cell>.json``.
"""

from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import loadgen  # noqa: E402
from run import ROOT, Cell, admit  # noqa: E402


def admits(cell: Cell, scale_ms: float) -> bool:
    import jax

    from repro.configs.registry import get_config
    from repro.models import model as M
    from repro.serving.engine import ServeEngine

    cfg = get_config(cell.conf["arch"]).reduced()
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    engine = ServeEngine(cfg, params, num_servers=cell.traffic["engine"]["servers"],
                         batching=True, paged=False)
    try:
        refused, _ = admit(cell, engine, loadgen.streams(cell.traffic, scale_ms))
    finally:
        engine.close()
    return not refused


def shortest(cell: Cell, lo_ms: float = 1.0, hi_ms: float = 1e6) -> float:
    if not admits(cell, hi_ms):
        raise SystemExit("not admitted even at the longest scale")
    while hi_ms - lo_ms > 1.0:
        mid = (lo_ms + hi_ms) / 2
        if admits(cell, mid):
            hi_ms = mid
        else:
            lo_ms = mid
    return float(int(hi_ms + 0.999))


if __name__ == "__main__":
    c = Cell.load(ROOT, sys.argv[1])
    s = shortest(c)
    print(f"{c.name}: period scale {s} ms; admitted at 90%: "
          f"{admits(c, 0.9 * s)}")
