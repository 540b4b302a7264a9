"""The solo serve cell for CPU rehearsals: ``_tiny``'s one-month trace and
float32 limit, at the ``--smoke`` agent's widths (2 layers, d=64, 4 heads,
d_ff 128, history 24 at 1800 s), three tenant slots six hours apart and
a 1-day horizon, so that a CPU window sees the whole cycle. Weight key 7
gives this agent a greedy submit share of 0.044 on a reactive run of the
tiny world."""
import _tiny

CELL = "serve-solo32.rtx-medium-transformer-pg"
execute = _tiny.execute


def spec(**traffic):
    s = _tiny.spec(CELL, tenants=3, sample=32, horizon_days=1, **traffic)
    s["config"]["agent"].update(n_layers=2, d_model=64, n_heads=4,
                                d_ff=128, history=24, weight_key=7)
    return s
