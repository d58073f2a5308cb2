"""Driver ``serve_closed``: a closed loop of ``clients`` callers against
``InferenceServer.submit`` on one ``register_decoder`` engine.

Each client sends its next request when its last one returns: callers
that wait for a reply (evaluation harnesses, batch pipelines, agent
loops). One thread drives all clients: a reply's done-callback puts it
on a queue with its time, and the loop thread books it and submits that
client's next request.

Set-up builds the model, the server and the engine, warms the engine's
programs, and runs the loop until ``clients`` requests have returned or
``ramp_s`` have passed (set-up the traffic needs: it breaks the
synchronised start). The window then opens on the same engine; a
request in flight at that moment counts, with its whole latency, when it
returns inside the window.

``correct``: once the window has closed and the engine is gone, a sample
of the requests it finished (drawn from the seed, the longest among
them) goes through the plain reference, one full forward pass over
prompt and served tokens; compared are the widest gap by which a served
token's logit lies below the reference's best (greedy tokens), and the
number of answers shorter than asked for.
"""

from __future__ import annotations

import queue
import time

import numpy as np

from benchmarks import gen, tracered
from benchmarks.harness import load_module


class ClosedLoop:
    def __init__(self, submit, requests, clients: int, shed=()) -> None:
        self.submit, self.requests, self.clients = submit, requests, clients
        self.shed = shed        # the exceptions by which a submit sheds
        self.done = queue.SimpleQueue()
        self.sent = 0
        self.records = []       # (request index, t_submit, t_done, tokens|None)

    def send(self) -> None:
        i = self.sent
        self.sent += 1
        prompt, max_new = self.requests[i % len(self.requests)]
        t = time.perf_counter()
        try:
            fut = self.submit({"prompt": prompt, "max_new": max_new})
        except self.shed as exc:        # shed: a failed request, booked
            self.done.put((i, t, time.perf_counter(), exc))
            return
        fut.add_done_callback(
            lambda f, i=i, t=t: self.done.put((i, t, time.perf_counter(), f)))

    def start(self) -> None:
        for _ in range(self.clients):
            self.send()

    def pump(self, until: float, enough=None) -> None:
        """Book replies and send each client's next request until the
        clock passes ``until`` (or ``enough()`` says so)."""
        while True:
            left = until - time.perf_counter()
            if left <= 0 or (enough is not None and enough()):
                return
            try:
                with tracered.span("wait_reply"):   # the engine's own thread works
                    i, t0, t1, f = self.done.get(timeout=min(left, 0.05))
            except queue.Empty:
                continue
            tokens = None
            if not isinstance(f, Exception) and f.exception() is None:
                tokens = np.asarray(f.result()["result"])
            self.records.append((i, t0, t1, tokens))
            with tracered.span("submit"):
                self.send()


def _request(requests, record):
    """``(prompt, max_new)`` of a booked record (the list wraps)."""
    return requests[record[0] % len(requests)]


def build(ctx, mv):
    from multiverso_tpu.serving import InferenceServer
    from multiverso_tpu.serving.batcher import OverloadedError

    t = ctx.traffic
    lm = load_module("drivers", "lm_train").make_model(ctx)
    ctx.mark("model")
    srv = InferenceServer("bench")
    eng = srv.register_decoder(
        "lm", lm, slots=t["slots"], max_prompt=t["max_prompt"],
        max_new=t["max_new"], prefill_token_budget=t["prefill_token_budget"],
        max_queue=max(256, 2 * t["clients"]), **t.get("engine", {}))
    eng.warmup()
    ctx.mark("warm")
    requests = gen.serve_requests(
        ctx.seed, t["requests"], ctx.config["vocab_size"], t["prompt_min"],
        t["prompt_max"], t["new_min"], t["new_max"], t["length_cycle"])
    loop = ClosedLoop(lambda payload: srv.submit("lm", payload), requests,
                      t["clients"], shed=(OverloadedError,))
    loop.start()
    loop.pump(time.perf_counter() + t["ramp_s"],
              enough=lambda: len(loop.records) >= t["clients"])
    ctx.mark("ramped")
    return {"lm": lm, "srv": srv, "eng": eng, "loop": loop,
            "requests": requests}


def window(state, ctx, seconds: float) -> dict:
    eng, loop = state["eng"], state["loop"]
    eng.reset_stats()
    before = len(loop.records)
    iters0 = eng.recorder.total if eng.recorder is not None else None
    t_open = time.perf_counter()
    loop.pump(t_open + seconds)
    t_close = time.perf_counter()
    stats = eng.stats()
    iters = (eng.recorder.total - iters0) if iters0 is not None else None
    mine = [r for r in loop.records[before:] if t_open <= r[2] <= t_close]
    good = [r for r in mine if r[3] is not None]
    lat_ms = sorted(1e3 * (r[2] - r[1]) if r[3] is not None else float("inf")
                    for r in mine)
    out_tokens = sum(len(r[3]) for r in good)
    elapsed = t_close - t_open
    # what the completed requests cost: prompt tokens prefilled, tokens
    # decoded, and the context each of them attended over
    P = np.asarray([len(_request(state["requests"], r)[0]) for r in good],
                   np.float64)
    N = np.asarray([len(r[3]) for r in good], np.float64)
    ctx.counters.update(
        attempted=len(mine), failed=len(mine) - len(good),
        completed=len(good), out_tokens=out_tokens, elapsed_s=elapsed,
        iterations=iters, prompt_tokens=float(P.sum()),
        prefill_context=float((P * (P + 1) / 2).sum()),
        decode_context=float((N * P + N * (N - 1) / 2).sum()),
        latency_p50_ms=lat_ms[len(lat_ms) // 2] if lat_ms else None,
        engine={k: stats[k] for k in (
            "ttft_p50_ms", "ttft_p99_ms", "itl_p50_ms", "slot_occupancy",
            "prefix_hits", "preemptions", "shed", "kv_pool_blocks",
            "step_traces", "prefill_traces", "decode_step_retraces")
            if k in stats})
    state["window"] = (t_open, t_close)
    if len(lat_ms) < 20:
        return {}
    rank = min(len(lat_ms) - 1, int(np.ceil(0.95 * len(lat_ms))) - 1)
    return {"serve_tokens_per_s": out_tokens / elapsed,
            "serve_req_p95_ms": lat_ms[rank]}


def release(state, ctx) -> dict:
    from multiverso_tpu.runtime import Session

    t_open, t_close = state["window"]
    loop, requests = state["loop"], state["requests"]
    srv = state.pop("srv")
    srv.stop()                  # drains what is in flight
    if srv in Session.get().servers:
        Session.get().servers.remove(srv)
    state.pop("eng")
    state.pop("lm")
    done = [r for r in loop.records
            if r[3] is not None and t_open <= r[2] <= t_close]
    short = sum(len(r[3]) != _request(requests, r)[1] for r in done)
    # the sample: the longest finished request, and others by the seed
    n = int(ctx.traffic["check_requests"])
    rng = np.random.default_rng(ctx.seed)
    by_len = sorted(done, key=lambda r: -(len(_request(requests, r)[0])
                                          + len(r[3])))
    pick = by_len[:1] + [by_len[1:][j] for j in rng.permutation(
        max(len(by_len) - 1, 0))[: n - 1]]
    prompts = [_request(requests, r)[0] for r in pick]
    return {"sequences": [np.concatenate([p, r[3]]).astype(np.int32)
                          for p, r in zip(prompts, pick)],
            "prompt_lens": [len(p) for p in prompts],
            "short_answers": short, "finished": len(done)}


def compare(gaps: list, got: dict, limits: dict) -> list:
    rows = [("token_logit_gap", max(gaps) if gaps else None),
            ("short_answers", float(got["short_answers"])
             if got["finished"] else None)]
    return [{"name": n, "value": v if v is None or np.isfinite(v) else None,
             "limit": limits[n]} for n, v in rows]


def check(got: dict, ctx) -> list:
    ref = load_module("reference", ctx.config_name)
    gaps = ref.token_gaps(ctx.config, ctx.seed31, got["sequences"],
                          got["prompt_lens"])
    ctx.counters["checked"] = {
        "requests": len(gaps), "gaps": gaps,
        "served_tokens": int(sum(len(s) - p for s, p in
                                 zip(got["sequences"], got["prompt_lens"])))}
    return compare(gaps, got, ctx.traffic["limits"])


def controls(got: dict, ctx) -> dict:
    """The control need not decode: at each position of the same prompts
    and served tokens, the gap (in the exact reference's logits) of the
    token that the reference with 8-bit-float matmuls puts first."""
    ref = load_module("reference", ctx.config_name)
    gaps = ref.token_gaps(ctx.config, ctx.seed31, got["sequences"],
                          got["prompt_lens"], compute="float8_e4m3fn")
    return {"control_float8_e4m3fn": compare(gaps, got, ctx.traffic["limits"])}
