"""A serving cell: one ``Engine.generate`` call over a request list longer
than the window can finish, every request due by the window's end.

The engine's own request lifecycle ends the call at ``--seconds``: requests
still queued end with no tokens, requests in flight end with the tokens they
have. Every token generated in the call counts toward the rate; requests
the deadline cut count as neither attempted nor failed.

``correct``: after the window, a sample of the requests it finished (drawn
from the seed, the longest among them) is run through the plain reference
over prompt and served tokens. The number compared is the widest gap by
which a served token's reference logit lies below the reference's best at
that position, in standard deviations of the reference's logits there.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench.lib import flops, peaks, program, report, tracing
from bench.lib.report import log as _log
from bench.lib import traffic as traffic_lib


def _generate(eng, reqs, deadline_s: float):
    batch = [program.request(r.prompt, r.max_new, r.task, deadline_s, i)
             for i, r in enumerate(reqs)]
    t0 = time.perf_counter()
    outs = eng.generate(batch)
    return outs, time.perf_counter() - t0


def _work(cfg, adapter, reqs, results, chunk: int) -> dict:
    """Required work of the requests, as far as their results show it: a
    request that produced tokens had its whole prompt computed; one cut
    before its first token shows nothing and is left out."""
    rows = [(len(r.prompt), int(res.n_generated),
             len(r.prompt) if res.n_generated > 0 else 0)
            for r, res in zip(reqs, results)]
    return flops.serve_forward(cfg, adapter, rows, chunk)


def warmup_requests(traffic: dict, vocab: int, seed: int, slots: int):
    """Fill every slot with staggered outputs so that slots complete every
    few steps and the deadline cuts the rest: this compiles admission, the
    decode loop, harvest and the deadline's slot kill."""
    reqs = traffic_lib.serve_requests(traffic, vocab, seed, slots, stream=1)
    plen = traffic["prompt"]["min"]
    return [traffic_lib.ServeRequest(r.prompt[:plen], 2 + 2 * i, r.task)
            for i, r in enumerate(reqs)]


def pick_checked(results, reqs, seed: int, spec: dict, finished: str):
    """Indices of the finished requests the reference checks: the longest,
    then others drawn from the seed until ``spec['tokens']`` served tokens
    or ``spec['max_requests']`` requests."""
    done = [i for i, r in enumerate(results)
            if r.status == finished and r.n_generated > 0]
    if not done:
        return []
    longest = max(done, key=lambda i: (results[i].n_generated,
                                       len(reqs[i].prompt)))
    rng = np.random.default_rng([int(seed) % 2 ** 63, 7])
    rest = [i for i in rng.permutation(done) if i != longest]
    pick, tok = [longest], results[longest].n_generated
    for i in rest:
        if tok >= spec["tokens"] or len(pick) >= spec["max_requests"]:
            break
        pick.append(int(i))
        tok += results[i].n_generated
    return pick


def served_gaps(ref, cores, prompts, served, tasks, top_of=None):
    """For each served token: the reference's best logit at its position
    minus the reference's logit of that token, in standard deviations of
    the reference's logits at that position (so the number reads the same
    at every width and logit scale). With ``top_of`` (a second
    reference) the token judged at each position is instead the one that
    ``top_of`` puts first. Returns (gaps, agreement with the reference's
    argmax)."""
    import jax.numpy as jnp
    seqs = [np.concatenate([p, s[:-1]]).astype(np.int32)
            for p, s in zip(prompts, served)]
    width = max(len(s) for s in seqs)
    toks = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    rows, cols, want = [], [], []
    for i, (p, s) in enumerate(zip(prompts, served)):
        for j in range(len(s)):
            rows.append(i)
            cols.append(len(p) - 1 + j)
            want.append(int(s[j]))
    task = jnp.asarray(np.asarray(tasks, np.int32))
    h = ref.hidden(toks, task, cores)
    lg = np.asarray(ref.logits_at(h, rows, cols))
    if top_of is not None:
        h2 = top_of.hidden(toks, task, top_of.cores())
        want = list(np.asarray(top_of.logits_at(h2, rows, cols)).argmax(-1))
    want = np.asarray(want)
    best = lg.max(-1)
    gaps = (best - lg[np.arange(len(want)), want]) / lg.std(-1)
    return gaps, float(np.mean(lg.argmax(-1) == want))


def run(cell, args, t_start: float, devs) -> dict:
    cfg, traffic = cell.config, cell.traffic
    adapter, eng_cfg = traffic["adapter"], traffic["engine"]
    vocab, seed = cfg["vocab_size"], args.seed
    st_names = program.statuses()
    t_in = time.perf_counter()

    p = program.params(cfg, adapter, seed)
    eng = program.engine(cfg, adapter, p, eng_cfg)
    del p
    t_eng = time.perf_counter()
    warm = warmup_requests(traffic, vocab, seed, eng_cfg["max_batch"])
    _, wt = _generate(eng, warm, traffic["warmup_seconds"])
    reqs = traffic_lib.serve_requests(traffic, vocab, seed,
                                      traffic["requests"], stream=0)
    setup_s = time.perf_counter() - t_start
    _log(f"set-up {setup_s:.2f}s: start to cell {t_in - t_start:.2f}s, "
         f"weights and engine {t_eng - t_in:.2f}s, warm-up generate "
         f"{wt:.2f}s (decode traces {eng.last_stats.decode_traces})")

    with tracing.span("generate"):
        outs, wall = _generate(eng, reqs, args.seconds)
    results, st = eng.last_results, eng.last_stats
    tokens = sum(len(o) for o in outs)
    by = {k: sum(r.status == v for r in results)
          for k, v in st_names.items()}
    _log(f"window {wall:.2f}s: {tokens} tokens, {by}, decode_calls "
         f"{st.decode_calls}, traces {st.decode_traces}, kv_blocks_peak "
         f"{st.kv_blocks_peak}, prefix_hit_tokens {st.prefix_hit_tokens}")
    if by["finished"] + by["timeout"] == len(reqs) and by["timeout"] == 0:
        _log("every request finished inside the window: the list is too "
             "short for this program")
    attempted = by["finished"] + by["failed"] + by["cancelled"]
    failed = by["failed"] + by["cancelled"] + max(
        0, st.numerics_faults - by["failed"])
    chunk = eng.sv.prefill_chunk
    e2e = {"serve_tokens_per_s": tokens / wall, "setup_s": setup_s}
    ctx = {"kind": "serve", "cfg": cfg, "traffic": traffic,
           "peak": peaks.peak(devs[0].device_kind),
           "device_kind": devs[0].device_kind,
           "window": {"seconds": wall, "tokens": tokens,
                      "stats": dict(decode_calls=st.decode_calls,
                                    tokens=st.tokens_generated),
                      "work": _work(cfg, adapter, reqs, results, chunk)}}
    breakdown = None
    tr_cfg = traffic["trace"]
    if args.trace:
        treqs = traffic_lib.serve_requests(traffic, vocab, seed,
                                           traffic["requests"], stream=2)
        with tracing.profiled(("generate",),
                              window_after=tr_cfg["skip_seconds"],
                              window_for=tr_cfg["seconds"]) as held:
            with tracing.span("generate"):
                _generate(eng, treqs, tr_cfg["skip_seconds"]
                          + tr_cfg["seconds"] + 1.0)
        tr = held.trace
        tres = eng.last_results
        _log(f"traced call: {sum(r.status == st_names['finished'] for r in tres)}"
             f" requests finished, decode_calls "
             f"{eng.last_stats.decode_calls}")
        ctx["trace"] = tr
        ctx["traced"] = {"work": _work(cfg, adapter, treqs,
                                       eng.last_results, chunk)}
        breakdown = tracing.breakdown(tr)
    device = report.device_info(devs, cell.chips)
    if args.trace:
        device["busy_s"] = ctx["trace"].busy_s()
        device["window_s"] = ctx["trace"].window_s

    pick = pick_checked(results, reqs, seed, traffic["check"],
                        st_names["finished"])
    prompts = [reqs[i].prompt for i in pick]
    served = [np.asarray(outs[i], np.int32) for i in pick]
    tasks = [reqs[i].task for i in pick]
    del eng, outs
    gc.collect()
    _log(f"checking {len(pick)} requests, "
         f"{sum(len(s) for s in served)} served tokens; device bytes in "
         f"use {report.bytes_in_use(devs[0])}")
    lim = cell.limits
    if pick:
        from bench.lib import cells as cells_lib
        ref_mod = cells_lib.reference(cell)
        ref = ref_mod.Reference(cfg, adapter, seed)
        t0 = time.perf_counter()
        gaps, agree = served_gaps(ref, ref.cores(), prompts, served, tasks)
        _log(f"reference {time.perf_counter() - t0:.1f}s; argmax "
             f"agreement {agree:.4f}")
        gap = float(gaps.max())
    else:
        gap = float("inf")
    checks = {"max_logit_gap": {"value": gap,
                                "limit": lim["max_logit_gap"]}}
    return {"correct": report.checks_ok(checks), "attempted": attempted,
            "failed": failed, "e2e": e2e, "ctx": ctx, "device": device,
            "checks": checks, "breakdown": breakdown,
            "sample": (prompts, served, tasks)}
