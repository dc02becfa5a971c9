"""Serving cells: `ContinuousBatchingServer.submit`/`step`, fed by the
traffic generator on the host clock.

Set-up makes the weights from the seed, builds the server, runs its own
warm-up, and then serves one short request of every prompt length the mix
draws, so that every shape the window uses (each prefill, each admission
scatter, the decode step) is compiled before the window opens.

The window either plays an open loop (`serve_open`: each request is due at
its arrival time, and is submitted once due whatever the server is doing) or
keeps a backlog (`serve_backlog`: the queue is topped up to a fixed depth
before every tick).  After each `step()` the harness reads how many tokens
each request has, which time-stamps every token on the host clock at the end
of the tick that made it.

Once the window has closed, a sample of the finished requests, drawn from the
seed with the longest among them, is run through the plain reference:
`served_gap` is the widest gap by which a served (greedy) token's reference
logit lies below the reference's best at that position, in standard
deviations of the reference's logits there.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from bench import harness, traffic
from bench.weights import make_params

__all__ = ["run", "sample_requests"]


def _longest(done: Dict[str, Dict]) -> str:
    """The finished request with most served tokens (ties: longest prompt,
    then the larger id)."""
    return max(done, key=lambda r: (len(done[r]["tokens"]), done[r]["prompt_len"], r))


def sample_requests(done: Dict[str, Dict], seed: int, min_tokens: int, max_requests: int) -> List[str]:
    """Request ids to check: the one with most served tokens, then others in
    the seed's order until `min_tokens` served tokens or `max_requests`."""
    if not done:
        return []
    ids = sorted(done)
    longest = _longest(done)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 3])
    rest = [ids[i] for i in rng.permutation(len(ids)) if ids[i] != longest]
    out, n = [longest], len(done[longest]["tokens"])
    for r in rest:
        if n >= min_tokens or len(out) >= max_requests:
            break
        out.append(r)
        n += len(done[r]["tokens"])
    return out


def _server(cell, params, model):
    from repro.launch.scheduler import ContinuousBatchingServer, ServeConfig

    mix = cell.mix
    slots, mpps = mix["slots"], mix["max_pages_per_seq"]
    scfg = ServeConfig(
        max_slots=slots,
        page_size=mix["page_size"],
        num_pages=1 + slots * mpps,
        max_pages_per_seq=mpps,
        queue_capacity=mix["queue_capacity"],
        default_deadline=mix["deadline_ticks"],
        warmup_prompt_lens=tuple(mix["prompt_lens"]),
    )
    return ContinuousBatchingServer(model, params, scfg)


class _Clock:
    """Token counts per request, read after every tick."""

    def __init__(self, server):
        self.server = server
        self.seen: Dict[str, int] = {}
        self.first: Dict[str, float] = {}
        self.last: Dict[str, float] = {}
        self.n_results = 0
        self.ticks: List[Any] = []  # (t, decode_contexts, prefill_lens)
        self.tokens = 0

    def observe(self, t: float, prompt_lens: Dict[str, int]) -> None:
        s = self.server
        counts = {seq.req.rid: (len(seq.tokens), seq.pos) for seq in s.decode_inputs()[0]}
        for rid in list(s.results)[self.n_results:]:
            counts[rid] = (len(s.results[rid].tokens), None)
        self.n_results = len(s.results)
        contexts, prefills = [], []
        for rid, (n, pos) in counts.items():
            before = self.seen.get(rid, 0)
            if n <= before:
                continue
            self.seen[rid] = n
            self.tokens += n - before
            self.last[rid] = t
            if before == 0:
                self.first[rid] = t
                prefills.append(prompt_lens.get(rid, 0))
                before = 1
            # Tokens past the first come from decode ticks; a slot decoding
            # its j-th new token attends over prompt + j keys.
            p = prompt_lens.get(rid, 0)
            contexts.extend(p + j for j in range(before, n))
        self.ticks.append((t, contexts, prefills))


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool, devices: List[Any],
        t_start: float, *, fault: str = "", control: str = None) -> Dict[str, Any]:
    """One run of a serving cell; returns the result line's fields.  `fault`
    plants a fault for the benchmark's own tests ("token": one served token
    altered where it is produced); `control` also reads the gap of the
    tokens that the reference in those numerics puts first (bench/control.py)."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.kernels.paged_attention import resolve_paged_impl
    from repro.launch.scheduler import Request
    from repro.models import get_model

    cfg, mix = cell.config, cell.mix
    arch = harness.arch_config(cfg)
    model = get_model(arch)
    abstract = model.abstract_params()
    params = make_params(abstract, cfg, seed)
    server = _server(cell, params, model)
    paged_impl = resolve_paged_impl(server.cfg.impl, interpret=server.cfg.interpret)
    server.warmup()
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 4])
    for p in mix["prompt_lens"]:
        prompt = rng.integers(0, cfg["vocab_size"], p).astype(np.int32)
        server.submit(Request(rid=f"warm-{p}", prompt=prompt, max_new_tokens=2))
    while server.pending:
        server.step()

    vocab = cfg["vocab_size"]
    open_loop = mix["kind"] == "serve_open"
    stream = traffic.request_stream(mix, seed)
    prompts: Dict[str, np.ndarray] = {}
    prompt_lens: Dict[str, int] = {}
    due: Dict[str, float] = {}
    wanted: Dict[str, int] = {}
    clock = _Clock(server)
    clock.n_results = len(server.results)
    nxt = next(stream)
    setup_s = time.monotonic() - t_start

    def submit(req, t):
        rid = f"r{req.index}"
        with TraceAnnotation("bench.generate"):
            toks = traffic.prompt_tokens(seed, req, vocab)
        prompts[rid], prompt_lens[rid], wanted[rid] = toks, req.prompt_len, req.max_new_tokens
        due[rid] = t
        with TraceAnnotation("bench.submit"):
            server.submit(Request(rid=rid, prompt=toks, max_new_tokens=req.max_new_tokens))

    from bench.tracing import Tracing

    tracer = Tracing(trace, cell.name)
    backlog = mix.get("backlog_factor", 0) * mix["slots"] + mix["slots"]
    with tracer:
        with TraceAnnotation("bench.window"):
            t0 = time.monotonic()
            t_end = t0 + seconds
            while True:
                now = time.monotonic()
                if now >= t_end:
                    break
                if open_loop:
                    while t0 + nxt.due_s <= now:
                        submit(nxt, t0 + nxt.due_s)
                        nxt = next(stream)
                else:
                    while server.pending < backlog:
                        submit(nxt, now)
                        nxt = next(stream)
                if server.pending:
                    with TraceAnnotation("bench.step"):
                        server.step()
                    clock.observe(time.monotonic(), prompt_lens)
                else:
                    with TraceAnnotation("bench.idle"):
                        time.sleep(max(0.0, min(t0 + nxt.due_s, t_end) - time.monotonic()))
            t1 = time.monotonic()
    window_s = t1 - t0
    while open_loop and t0 + nxt.due_s <= t1:  # due, but the window closed first
        due[f"r{nxt.index}"] = t0 + nxt.due_s
        nxt = next(stream)
    mem = harness.memory_peak(devices)
    problems = harness.health_problems(cfg, paged_impl)

    results = {rid: r for rid, r in server.results.items() if rid in due}
    failed = sum(1 for r in results.values() if r.status != "ok")
    done = {
        rid: {"tokens": list(r.tokens), "prompt_len": prompt_lens[rid]}
        for rid, r in results.items()
        if r.status == "ok"
    }
    if fault == "token" and done:
        rid = _longest(done)
        done[rid]["tokens"][-1] = (done[rid]["tokens"][-1] + 1) % vocab
    bad_len = [rid for rid, d in done.items() if len(d["tokens"]) != wanted[rid]]
    if bad_len:
        problems.append(f"{len(bad_len)} finished requests with another token count than asked")
    server = params = clock.server = None  # free the pools before the reference
    gc.collect()

    ttft, tpot = [], []
    for rid, t_due in due.items():
        first = clock.first.get(rid)
        ttft.append((first if first is not None and first <= t1 else t1) - t_due)
        n = clock.seen.get(rid, 0)
        if n >= 3:
            tpot.append((clock.last[rid] - clock.first[rid]) / (n - 1))
    run_rec = harness.RunRecord(
        cell=cell,
        peaks=None,
        window_s=window_s,
        setup_s=setup_s,
        data={
            "tokens": clock.tokens,
            "ttft_s": ttft,
            "tpot_s": tpot,
            "ticks": clock.ticks,
            "due": due,
            "window_t0": t0,
            "window_t1": t1,
        },
    )
    tracer.fill(run_rec)

    chk = mix["check"]
    sample = sample_requests(done, seed, chk["min_tokens"], chk["max_requests"])
    gaps = _reference_gaps(cfg, abstract, seed, sample, done, prompts, control)
    if control and gaps is not None:
        readings = {"served_gap": gaps[0], "control_gap": gaps[1]}
    else:
        readings = {"served_gap": gaps}
    compared = harness.check(readings, cell.limits)
    return {
        "run": run_rec,
        "attempted": len(due),
        "failed": failed,
        "memory_peak_bytes": mem,
        "compared": compared,
        "problems": problems,
    }


def _reference_gaps(cfg, abstract, seed, sample, done, prompts, control=None):
    """Widest served-token gap over the sampled requests (and the control's,
    with `control`), each request's sequence padded at its end to a multiple
    of 512 so that a few programs serve every length (causal: the padding
    changes no earlier position)."""
    import jax

    from bench.references import dense_transformer as ref

    if not sample:
        return None
    params = make_params(abstract, cfg, seed)
    worst, cworst = 0.0, 0.0
    for rid in sample:
        toks = done[rid]["tokens"]
        g = ref.served_gaps(params, prompts[rid], toks, cfg, control=control, pad_to=512)
        worst = max(worst, g["served_gap"])
        cworst = max(cworst, g.get("control_gap", 0.0))
    del params
    gc.collect()
    jax.clear_caches()
    return (worst, cworst) if control else worst
