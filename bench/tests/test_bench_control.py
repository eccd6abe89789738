"""The control comes out not correct under the cell's own limits.

The control is the reference one precision step below the cell's bf16:
int8 products (W8A8, ``bench/reference.py``), put in the program's
place at the same prompts and tokens.  Here it runs at a small size on
the CPU after a sound serving run; on the chip it was read at the
cell's own size (PERF.md, section 6)."""
from __future__ import annotations

import bench_tiny
import pytest

from bench.drivers import serve


@pytest.mark.parametrize("seed", [2**31 + 5, 2**31 + 6, 2**31 + 7])
def test_control_fails_where_the_program_passes(seed):
    config = bench_tiny.serve_config()
    limits = config["correct"]
    ctx = bench_tiny.context(config, bench_tiny.MIX, seed=seed)
    srv = serve.Server(ctx)
    srv.warm()
    rec = srv.window(ctx.seconds)
    srv.grace(rec["requests"], rec["rows"])
    prompts = {r.id: r.prompt for r in srv.reqs}
    args = (seed, srv.s, srv.sample, rec["requests"], rec["rows"], prompts,
            srv.max_seq, config["dtype"])
    srv.free()
    program = serve.compare(*args)
    control = serve.compare(*args, control=True)
    names = ("served_gap_mean", "logit_err")
    assert all(program[k] <= limits[k] for k in names), program
    assert any(control[k] > limits[k] for k in names), control
