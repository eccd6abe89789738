"""Serving cells: an open loop of requests through ``ServeEngine``.

Set-up makes the weights on the device from the seed, builds the engine
as the configuration says, and warms every prompt length of the cell's
traffic in every decode slot.  The window then submits each request
when it is due (``submit``), runs ``step`` whenever work waits, and
reads new tokens with ``poll``; each call sits in a
``jax.profiler.TraceAnnotation`` (``bench.submit``, ``bench.step.admit``
when the step will admit a waiting request, else ``bench.step.decode``,
``bench.poll``, ``bench.wait``).  Latency runs from a request's due
time.  After the window no request is submitted; the engine runs until
the submitted ones finish (a minute at most), and a sample of them,
drawn from the seed and holding the longest answer, is compared with
the reference.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import reference, traffic, weights
from bench.drivers.common import check, program_config
from bench.sizes import from_config

GRACE_S = 60.0
SAMPLE_TOKENS = 300      # served tokens the comparison covers, at least
SAMPLE_MAX = 16          # requests in the sample, at most
SAMPLE_DUE = 0.6         # sampled requests are due in this share of it


def choose_sample(reqs: list, seed: int, seconds: float) -> list:
    """Ids of the requests to compare: the one with the longest answer
    among those due early in the window, then others drawn from the
    seed until they hold ``SAMPLE_TOKENS`` answer tokens."""
    early = [r for r in reqs if r.due_s < SAMPLE_DUE * seconds] or reqs
    first = max(early, key=lambda r: (r.max_new_tokens, len(r.prompt)))
    rng = np.random.default_rng([int(seed), 7])
    rest = [early[i] for i in rng.permutation(len(early))
            if early[i] is not first]
    out, tokens = [first], first.max_new_tokens
    for r in rest:
        if tokens >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX:
            break
        out.append(r)
        tokens += r.max_new_tokens
    return [r.id for r in out]


class Server:
    """The engine with this cell's weights, traffic and sample."""

    def __init__(self, ctx):
        import jax.numpy as jnp
        from repro.serving import ServeConfig, ServeEngine

        self.ctx = ctx
        c = ctx.config
        self.s = from_config(c)
        sv = c["serve"]
        self.slots = sv["decode_slots"]
        self.max_seq = sv["max_seq"]
        self.reqs = traffic.requests(ctx.traffic, ctx.seed, ctx.seconds,
                                     self.s.vocab)
        self.sample = choose_sample(self.reqs, ctx.seed, ctx.seconds)
        self.params = weights.make_program_params(ctx.seed, self.s,
                                                  jnp.dtype(c["dtype"]))
        self.engine = ServeEngine(
            program_config(c, self.s), self.params,
            ServeConfig(max_seq=self.max_seq, decode_slots=self.slots,
                        cache_dtype=sv["cache_dtype"]))

    def request(self, r, max_new=None):
        from repro.serving import Request, SamplingParams
        return Request(id=r.id, tokens=r.prompt,
                       sampling=SamplingParams(temperature=0.0),
                       max_new_tokens=max_new or r.max_new_tokens)

    def warm(self) -> None:
        """Every prompt length of the traffic, in every decode slot."""
        lens = sorted({len(r.prompt) for r in self.reqs})
        n = max(len(lens), self.slots)
        rng = np.random.default_rng(0)
        for j in range(n):
            r = traffic.Req(f"warm{j}", 0.0, rng.integers(
                0, self.s.vocab, lens[j % len(lens)], dtype=np.int32), 2)
            self.engine.submit(self.request(r))
        while self.engine.step():
            pass
        self.engine.poll()

    def window(self, seconds: float, trace_dir=None) -> dict:
        """The open loop; returns per-request and per-step records.
        Times are seconds after the window opened."""
        import jax
        ann = jax.profiler.TraceAnnotation
        eng, reqs = self.engine, self.reqs
        recs = {r.id: {"due": r.due_s, "submit": None, "t": [],
                       "tokens": [], "prompt": len(r.prompt),
                       "finished": None} for r in reqs}
        rows = {rid: [] for rid in self.sample}
        steps = []
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        t0 = self.t0 = time.perf_counter()
        i, n = 0, len(reqs)
        with ann("bench.window"):
            while True:
                now = time.perf_counter() - t0
                if now >= seconds:
                    break
                if i < n and reqs[i].due_s <= now:
                    with ann("bench.submit"):
                        while i < n and reqs[i].due_s <= now:
                            eng.submit(self.request(reqs[i]))
                            recs[reqs[i].id]["submit"] = now
                            i += 1
                if eng.sched.idle:
                    nxt = reqs[i].due_s if i < n else seconds
                    with ann("bench.wait"):
                        time.sleep(max(0.0, min(nxt, seconds) - now))
                    continue
                steps.append(self._step(t0, recs, rows, ann))
        window_s = time.perf_counter() - t0
        if trace_dir:
            jax.profiler.stop_trace()
        return {"requests": recs, "steps": steps, "rows": rows,
                "window_s": window_s, "submitted": i}

    def _step(self, t0, recs, rows, ann) -> dict:
        eng = self.engine
        admit = bool(eng.sched.waiting) and \
            len(eng.sched.running) < self.slots
        pre, dec = eng.counters["prefills"], eng.counters["decode_steps"]
        ts = time.perf_counter()
        with ann("bench.step.admit" if admit else "bench.step.decode"):
            eng.step()
        te = time.perf_counter()
        decoded = eng.counters["decode_steps"] > dec
        stepped = list(eng.last_logits) if decoded else []
        for rid in stepped:
            if rid in rows:
                rows[rid].append(np.array(eng.last_logits[rid]))
        with ann("bench.poll"):
            out = eng.poll()
        tp = time.perf_counter() - t0
        for rid, (status, fresh) in out.items():
            rec = recs.get(rid)
            if rec is None:
                continue
            rec["t"] += [tp] * len(fresh)
            rec["tokens"] += fresh
            if status == "finished" and rec["finished"] is None:
                rec["finished"] = tp
        # a request that decoded attends to its prompt and all its
        # tokens but the newest
        lengths = [recs[rid]["prompt"] + len(recs[rid]["tokens"]) - 1
                   for rid in stepped if rid in recs]
        return {"t0": ts - t0, "t1": te - t0,
                "prefills": eng.counters["prefills"] - pre,
                "decoded": decoded, "lengths": lengths}

    def grace(self, recs: dict, rows: dict, limit_s: float = GRACE_S):
        """Run until every submitted request has finished (untimed)."""
        import jax
        ann = jax.profiler.TraceAnnotation
        t_end = time.perf_counter() + limit_s
        while not self.engine.sched.idle and time.perf_counter() < t_end:
            self._step(self.t0, recs, rows, ann)

    def free(self) -> None:
        del self.engine, self.params
        gc.collect()


def compare(seed: int, s, sample: list, recs: dict, rows: dict,
            prompts: dict, pad_to: int, weight_dtype,
            control: bool = False) -> dict:
    """Readings of the sampled requests against the reference.

    ``served_gap``: the widest gap by which a served token's logit lies
    below the reference's best at its position; ``served_gap_mean``:
    that gap's mean over every served token.  ``logit_err``: the
    largest ``max|engine - reference|`` of a decode step's logits, as a
    share of that row's largest reference logit.  With ``control``, the
    same readings of the control (the reference with int8 products,
    ``reference.logits_at(int8=True)``) take the engine's place: its
    own first choice at every position, and its rows."""
    import jax.numpy as jnp
    done = [rid for rid in sample if recs[rid]["finished"] is not None]
    seqs, want = [], []
    for rid in done:
        p, toks = prompts[rid], recs[rid]["tokens"]
        seqs.append(np.concatenate([p, np.asarray(toks[:-1], np.int32)]))
        want.append(np.arange(len(p) - 1, len(p) - 1 + len(toks)))
    ref = reference.logits_at(seed, s, seqs, want, pad_to, weight_dtype)
    ctl = None
    if control:
        ctl = reference.logits_at(seed, s, seqs, want, pad_to, weight_dtype,
                                  dtype=jnp.bfloat16,
                                  precision=reference.DEFAULT, int8=True)
    gaps, err = [], 0.0
    for k, rid in enumerate(done):
        r = ref[k]
        toks = recs[rid]["tokens"]
        if ctl is None:
            chosen, prog = np.asarray(toks), rows[rid]
        else:
            chosen, prog = np.argmax(ctl[k], axis=-1), ctl[k][1:]
        gaps.append(r.max(axis=-1) - r[np.arange(len(r)), chosen])
        # decode rows give tokens 1..n-1; token 0 comes from the prefill
        for ref_row, row in zip(r[1:], prog):
            row = np.asarray(row[:s.vocab], np.float32)
            err = max(err, float(np.max(np.abs(row - ref_row))
                                 / np.max(np.abs(ref_row))))
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"served_gap": float(gaps.max(initial=0.0)),
            "served_gap_mean": float(gaps.mean()) if gaps.size else None,
            "logit_err": err, "requests": len(done),
            "missing": len(sample) - len(done), "tokens": int(gaps.size)}


def run(ctx) -> dict:
    from bench.harness import peak_bytes
    srv = Server(ctx)
    srv.warm()
    c_s, c_n, c_hits = ctx.clock.take()
    t_open = time.perf_counter()
    setup_s = t_open - ctx.t_start
    ctx.log(f"set-up {setup_s:.3f} s: compiles {c_n} taking {c_s:.3f} s, "
            f"persistent-cache hits {c_hits}; {len(srv.reqs)} requests "
            f"due, sample {srv.sample}")
    rec = srv.window(ctx.seconds, ctx.trace_dir)
    w_s, w_n, _ = ctx.clock.take()
    ctx.log(f"window {rec['window_s']:.3f} s: {len(rec['steps'])} steps, "
            f"{rec['submitted']} submitted, compiles inside {w_n} "
            f"({w_s:.3f} s)")
    srv.grace(rec["requests"], rec["rows"])
    peak = peak_bytes(ctx.devices)
    prompts = {r.id: r.prompt for r in srv.reqs}
    sample, s = srv.sample, srv.s
    unfinished = sum(1 for r in rec["requests"].values()
                     if r["submit"] is not None and r["finished"] is None)
    srv.free()
    pad_to = -(-srv.max_seq // 512) * 512
    got = compare(ctx.seed, s, sample, rec["requests"], rec["rows"],
                  prompts, pad_to, ctx.config["dtype"])
    limits = ctx.config["correct"]
    checks = [check(k, got[k], limits[k]) for k in ("served_gap_mean",
                                                     "logit_err")]
    checks.append(check("sample_missing", got["missing"], 0))
    ctx.log(f"compared {got['requests']} requests, {got['tokens']} "
            f"served tokens; widest served gap {got['served_gap']!r} "
            f"(not compared: see PERF.md)")
    return {"correct": all(c["ok"] for c in checks),
            "attempted": rec["submitted"], "failed": unfinished,
            "checks": checks, "records": rec, "setup_s": setup_s,
            "window_s": rec["window_s"], "compiles_in_window": w_n,
            "memory_peak_bytes": peak, "sizes": s}
