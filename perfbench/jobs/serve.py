"""Open-loop serving: one ``DecodeServer`` with the radix prefix cache, driven
as ``cli/serve_main.py`` drives it (admit what is waiting into free slots,
then one decode round), by a schedule of arrivals fixed before the window.

Every request is timed from when it was DUE, not from when the loop got to
it; a request that fails, is refused, or is still unfinished when the
drain limit passes misses every limit.  The loop is one thread: the load
generator, the admission queue and the server share it, and
``gen.late_p95_ms`` reports how late the generator noticed its arrivals.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from .. import correct, program, reduce, traffic_gen
from ..harness import say, tiny


class Loop:
    """The serving loop and its per-request record."""

    def __init__(self, ctx, server):
        self.ctx = ctx
        self.server = server
        self.pending = collections.deque()   # (request, noticed_at)
        self.live: dict[int, dict] = {}  # server request id -> record
        self.records: list[dict] = []
        self.occupancy: list[int] = []   # active slots at each round
        self.prefill_rounds = 0
        self.rounds = 0
        self.failed = 0

    def admit(self, opened: float) -> int:
        admitted = 0
        while self.pending and self.server.has_free_slot:
            request, noticed = self.pending.popleft()
            record = {"index": request.index, "due": opened + request.due_s,
                      "noticed": noticed, "max_new": request.max_new,
                      "prompt_len": len(request.prompt), "tokens": [],
                      "times": []}
            self.records.append(record)
            try:
                with self.ctx.annotate("admit"):
                    rid = self.server.submit(request.prompt,
                                             max_new_tokens=request.max_new)
            except (RuntimeError, ValueError) as exc:
                record["error"] = str(exc)
                self.failed += 1
                continue
            now = time.time()
            admitted += 1
            if rid in self.server.finished():
                record["tokens"] = list(self.server.result(rid))
                record["times"] = [now] * len(record["tokens"])
                record["done"] = now
                continue
            record["tokens"].append(int(self.server.peek(rid)[0]))
            record["times"].append(now)
            self.live[rid] = record
        return admitted

    def round(self, admitted: int) -> None:
        if self.server.idle:
            return
        self.occupancy.append(self.server.active)
        with self.ctx.annotate("round"):
            emitted = self.server.step()
        now = time.time()
        self.rounds += 1
        self.prefill_rounds += 1 if admitted else 0
        for rid, token in emitted:
            record = self.live[rid]
            record["tokens"].append(int(token))
            record["times"].append(now)
        for rid in self.server.finished():
            record = self.live.pop(rid)
            self.server.result(rid)
            record["done"] = now

    def drive(self, schedule, opened: float, until: float,
              trace_until: float) -> None:
        """Arrivals from ``schedule`` (due offsets from ``opened``) until
        every one has been noticed and either finished or ``until``
        passed.  The profiler, if on, stops at ``trace_until``."""
        nxt = 0
        while True:
            now = time.time()
            if now >= trace_until:
                self.ctx.stop_trace()
            while nxt < len(schedule) and opened + schedule[nxt].due_s <= now:
                self.pending.append((schedule[nxt], now))
                nxt += 1
            admitted = self.admit(opened)
            if nxt == len(schedule) and not self.pending and not self.live:
                return
            if now > until:
                return
            if self.server.idle and not self.pending:
                # nothing in flight: sleep to the next arrival
                time.sleep(max(0.0, min(
                    0.002, opened + schedule[nxt].due_s - time.time())))
                continue
            self.round(admitted)


def build(ctx, systems):
    """The model, its weights (one jitted call from the seed, in the
    serving dtype) and the server, with the system prompts resident in the
    prefix cache and every shape the traffic can produce run once."""
    import jax

    from parameter_server_distributed_tpu.models.serving import DecodeServer

    traffic, config = ctx.traffic, ctx.config
    spec = traffic["server"]
    model = program.build_model(config, remat=False)
    params = program.make_weights(model, ctx.seed)
    jax.block_until_ready(params)
    ctx.setup.mark("weights")
    server = DecodeServer(
        model, params, slots=spec["slots"], max_len=spec["max_len"],
        prompt_cache=spec["prompt_cache"],
        prefix_cache_bytes=spec["prefix_cache_bytes"],
        seed=program.program_seed(ctx.seed))
    for tokens in systems:
        server.submit(tokens, max_new_tokens=1)
        for rid in server.finished():
            server.result(rid)
    warm = Loop(ctx, server)
    warm.pending.extend(
        (r, time.time()) for r in traffic_gen.warmup_requests(
            traffic, config["vocab_size"], ctx.seed, systems))
    warm.drive([], time.time(), float("inf"), float("inf"))
    if warm.failed:
        raise RuntimeError(f"{warm.failed} warm-up requests failed: "
                           f"{[r.get('error') for r in warm.records]}")
    return model, params, server


def offer(ctx, server, schedule, opened: float) -> Loop:
    """The schedule, offered from ``opened``; returns when every request
    has finished or the drain limit has passed."""
    loop = Loop(ctx, server)
    loop.drive(schedule, opened,
               opened + ctx.seconds + ctx.traffic["drain_seconds"],
               opened + ctx.traffic["trace_seconds"])
    return loop


def latencies(loop: Loop, schedule, opened: float, closed: float,
              seconds: float, slo: dict) -> dict:
    """Per-request records to samples.  Every request is timed from when
    it was due; one that failed, was never sent or did not finish misses
    the limits."""
    ttft, itl, late, ok = [], [], [], 0
    halves = ([], [])
    exact = True
    for record in loop.records:
        finished = "done" in record and "error" not in record
        exact &= (not finished) or len(record["tokens"]) == record["max_new"]
        late.append(1e3 * (record["noticed"] - record["due"]))
        if not record["times"]:
            continue
        first = 1e3 * (record["times"][0] - record["due"])
        ttft.append(first)
        halves[record["due"] - opened >= seconds / 2].append(first)
        gaps = [1e3 * (b - a) for a, b
                in zip(record["times"], record["times"][1:])]
        itl.extend(gaps)
        if (finished and first <= slo["ttft_ms"]
                and (not gaps or max(gaps) <= slo["itl_ms"])):
            ok += 1
    unfinished = sum(1 for r in loop.records if "done" not in r)
    unsent = len(schedule) - len(loop.records)

    def p(values, q):
        return reduce.percentile(values, q) if values else None

    return {
        "ttft_ms": ttft, "itl_ms": itl, "late_ms": late, "slo_ok": ok,
        "exact": bool(exact), "unsent": unsent, "unfinished": unfinished,
        "failed": loop.failed + unfinished + unsent,
        "summary": {
            "sent": len(loop.records), "unfinished": unfinished,
            "unsent": unsent, "errors": loop.failed,
            "ttft_samples": len(ttft), "itl_samples": len(itl),
            "rounds": loop.rounds, "window_s": closed - opened,
            "ttft_p50_ms": p(ttft, 50), "ttft_p95_ms": p(ttft, 95),
            "itl_p50_ms": p(itl, 50), "itl_p90_ms": p(itl, 90),
            "itl_p95_ms": p(itl, 95), "itl_p99_ms": p(itl, 99),
            "ttft_p50_first_half_ms": p(halves[0], 50),
            "ttft_p50_second_half_ms": p(halves[1], 50),
            "ttft_p95_first_half_ms": p(halves[0], 95),
            "ttft_p95_second_half_ms": p(halves[1], 95),
            "backlog_at_close": len(loop.pending) + len(loop.live),
            "slo_ok_pct": 100.0 * ok / max(1, len(schedule)),
            "mean_occupancy": (sum(loop.occupancy) / len(loop.occupancy)
                               if loop.occupancy else 0.0),
            "completed_tokens_per_s": sum(
                len(r["tokens"]) for r in loop.records) / (closed - opened),
        }}


def shrink(config: dict, traffic: dict) -> tuple[dict, dict]:
    config, traffic = tiny(config, traffic)
    traffic["server"].update(slots=4, max_len=128,
                             prefix_cache_bytes=1 << 24)
    traffic["sessions"]["system_prompts"] = [20, 24, 28, 32]
    traffic["user_tokens"].update(median=8, min=2, max=16)
    traffic["output_tokens"].update(median=6, min=2, max=12)
    traffic["check"]["served_tokens"] = 4
    traffic.update(drain_seconds=30, trace_seconds=1)
    return config, traffic


def run(ctx) -> dict:
    traffic, config = ctx.traffic, ctx.config
    vocab = config["vocab_size"]
    systems = traffic_gen.system_prompts(traffic, vocab, ctx.seed)
    schedule = traffic_gen.serve_schedule(traffic, vocab, ctx.seed,
                                          ctx.seconds, systems)
    say(detail="serve_traffic", requests=len(schedule),
        rate_per_s=traffic["arrivals"]["rate_per_s"],
        prompt_tokens=traffic_gen.describe_lengths(
            [len(r.prompt) for r in schedule]),
        output_tokens=traffic_gen.describe_lengths(
            [r.max_new for r in schedule]))
    ctx.setup.mark("traffic")
    model, params, server = build(ctx, systems)

    stats_before = dict(server.stats)
    before = program.registry_snapshot()
    opened = ctx.open_window()
    ctx.start_trace()
    loop = offer(ctx, server, schedule, opened)
    closed = time.time()
    ctx.close_window(closed)
    after = program.registry_snapshot()
    stats_after = dict(server.stats)
    traced = ctx.finish_trace()
    seen = latencies(loop, schedule, opened, closed, ctx.seconds,
                     traffic["slo"])
    say(detail="serve_window", **seen["summary"])

    # correctness, outside the window: one fresh request through the live
    # server (prefix-cache extension, splice, decode rounds through the
    # cache) against the reference's full forward over prompt + served
    # tokens; then, with the server gone, the model's forward logits
    prompt = np.concatenate([schedule[0].prompt,
                             np.asarray([vocab - 1], np.int32)])
    rid = server.submit(prompt,
                        max_new_tokens=traffic["check"]["served_tokens"])
    served = server.run_to_completion()[rid]
    del server, loop.server
    margin = correct.served_tokens_margin(config, params, prompt, served)
    del params
    check = correct.compare_forward(config, model, ctx.seed,
                                    traffic["check"])
    checks = {
        "logits": check,
        "served_margin_std": margin,
        "served_near_tie_tolerance": correct.NEAR_TIE_TOLERANCE,
        "served_ok": margin <= correct.NEAR_TIE_TOLERANCE,
        "every_request_exactly_its_tokens": seen["exact"],
        "all_sent": seen["unsent"] == 0,
    }
    # every statistic a serving cell may be judged on; BENCHMARK.json says
    # which of them are end-to-end metrics of which cell
    end_to_end = {}
    if seen["ttft_ms"] and seen["itl_ms"]:
        end_to_end = {
            f"{name}_p{q}_ms": reduce.percentile(seen[f"{name}_ms"], q)
            for name in ("ttft", "itl") for q in (50, 90, 95, 99)}
        end_to_end["serve_tokens_per_s"] = seen["summary"][
            "completed_tokens_per_s"]
    observed = {
        "window_s": closed - opened, "late_ms": seen["late_ms"],
        "ttft_ms": seen["ttft_ms"],
        "slo_ok": seen["slo_ok"], "sent": len(schedule),
        "occupancy": loop.occupancy, "slots": traffic["server"]["slots"],
        "rounds": loop.rounds, "prefill_rounds": loop.prefill_rounds,
        "stats_before": stats_before, "stats_after": stats_after,
        "registry_before": before, "registry_after": after,
        "trace": traced, "memory": ctx.memory,
    }
    return {"attempted": len(schedule), "failed": seen["failed"],
            "checks": checks, "end_to_end": end_to_end,
            "observed": observed, "traced": traced}
