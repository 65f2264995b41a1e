"""Continuous-batching serving process (`pst-serve`) — the operational
face of models/serving.DecodeServer.

    pst-serve --model=small_lm [--ckpt=... | --ckpt-dir=... |
              --hf-gpt2=<checkout>] \\
              [--slots=8] [--max-len=2048] [--temperature=0.8 --top-k=40] \\
              [--quant=int8] [--kv-cache=int8] [--eos=ID] \\
              [--prompt-cache=N]   # repeated prompts skip prefill (LRU)
              [--draft-model=tiny_lm --draft-ckpt=... --draft-len=4]
              [--no-adaptive-draft] [--draft-cost-ratio=R]
              [--fused-rounds=N]  # amortize N decode rounds per device
                                  # dispatch when no requests are waiting
                                  # (token-exact; higher throughput,
                                  # blockier streaming)
              # speculative serving: --draft-len is the depth CAP; the
              # server adapts per-round depth from the measured accept
              # rate (disabling speculation when it cannot pay) unless
              # --no-adaptive-draft pins it

Line protocol (JSONL on stdin/stdout — composable behind any transport):

    -> {"id": 1, "prompt": "hello"}             # or "tokens": [1,2,3]
    -> {"id": 2, "tokens": [5,6], "max_new": 32}
    -> {"id": 4, "prompt": "hi", "temperature": 0.7, "stop": [13]}
    <- {"id": 1, "token": 42}                   # streamed as decoded
    <- {"id": 1, "done": true, "text": "..."}   # or "tokens": [...]
    <- {"id": 3, "error": "..."}                # bad request

Per-request "temperature" overrides the server default for that request
only (temperatures are a traced per-slot input — mixed batches share one
compiled step; rejected in speculative mode, where the accept rule is
compiled for the server temperature).  "stop": [ids...] finishes that
request at any of the listed tokens, alongside the global --eos.

Requests are admitted the moment a slot frees (continuous batching — one
compiled ragged decode step serves every in-flight request); stdin close
drains the in-flight work and exits.  Reference has no serving runtime at
all (no model, no inference — reference src/worker.cpp:316-329); this
completes the train -> checkpoint -> serve loop as a process main in the
reference's CLI style (component #10, SURVEY.md §2).
"""

from __future__ import annotations

import json
import logging
import queue
import sys
import threading
import time

from ..config import parse_argv, require_flag_value
from ..obs import flight

KNOWN_FLAGS = frozenset({
    "model", "dtype", "scan-layers", "no-scan-layers", "seed", "ckpt",
    "ckpt-dir", "avg-last", "hf-gpt2", "slots", "max-len", "temperature",
    "top-k", "top-p", "eos", "quant", "kv-cache", "default-max-new",
    "lora-alpha", "draft-lora-alpha", "prompt-cache",
    "draft-model", "draft-ckpt", "draft-seed", "draft-len",
    "no-adaptive-draft", "draft-cost-ratio", "fused-rounds",
    "follow", "subscriber-id",
    # decode fleet mode (fleet/, ISSUE 14): serve the psdt_fleet.Decode
    # gRPC service instead of the stdin/stdout line protocol, and
    # (optionally) register with a coordinator for routing/autoscaling
    "serve-port", "coordinator", "server-id",
})


def _reader(out_q: "queue.Queue[tuple | None]") -> None:
    """stdin -> request queue as TYPED items — ("req", dict) or
    ("err", message) — with None marking end of input.  The out-of-band
    tag means no request payload can alias the error channel (an in-band
    magic key could), a valid-JSON scalar/array becomes a per-line error
    instead of crashing the loop, and a `null` line can never be confused
    with the EOF sentinel."""
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError as exc:
            out_q.put(("err", str(exc)))
            continue
        if not isinstance(obj, dict):
            out_q.put(("err",
                       f"request must be a JSON object, got {line[:80]!r}"))
            continue
        out_q.put(("req", obj))
    out_q.put(None)


def _emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(levelname)s "
                               "%(message)s")
    _, flags = parse_argv(argv)
    if "help" in flags:
        print(__doc__)
        return 0
    # bare --lora-alpha would merge with alpha 1 instead of the trained
    # value, silently mis-scaling every adapter
    require_flag_value(argv, "--lora-alpha", "--draft-lora-alpha",
                       hint="the ALPHA the run trained with")
    require_flag_value(argv, "--draft-cost-ratio",
                       hint="draft/target per-token cost for the "
                            "adaptive depth controller")
    # bare --fused-rounds would parse as 1 and silently disable the
    # feature the user asked for
    require_flag_value(argv, "--fused-rounds",
                       hint="decode rounds per device dispatch, e.g. "
                            "--fused-rounds=8")
    # bare --follow would silently serve boot weights forever
    require_flag_value(argv, "--follow",
                       hint="the training PS address to track, e.g. "
                            "--follow=10.0.0.5:50051")
    # bare --serve-port parses as 1 and binds an arbitrary low port;
    # bare --coordinator would register against localhost silently
    require_flag_value(argv, "--serve-port", "--coordinator",
                       "--server-id",
                       hint="fleet mode, e.g. --serve-port=50070 "
                            "--coordinator=10.0.0.5:50052 --server-id=0")
    unknown = set(flags) - KNOWN_FLAGS
    if unknown:
        raise SystemExit(f"unknown flag(s): {', '.join(sorted(unknown))}; "
                         f"--help lists the accepted flags")

    from ..models.serving import DecodeServer
    from ..utils.compile_cache import enable_compile_cache
    from .generate_main import load_hf, load_params, match_layout

    enable_compile_cache()

    hf_tok = None
    if flags.get("hf-gpt2"):
        model, params, hf_tok = load_hf(flags)
        source = f"HF GPT-2 checkpoint {flags['hf-gpt2']}"
    else:
        from ..models.registry import get_model_and_batches
        from ..models.transformer import Transformer
        model, _ = get_model_and_batches(
            flags.get("model", "small_lm"), 1, dtype=flags.get("dtype", ""),
            scan=(False if "no-scan-layers" in flags
                  else True if "scan-layers" in flags else None))
        if not isinstance(model, Transformer):
            raise ValueError(f"--model={flags.get('model')!r} is not an LM")
        params, source = load_params(flags, model,
                                     int(flags.get("seed", 0)))
        params = match_layout(model, params)
    # one binding for both weight paths — the boot params here and every
    # follower hot swap below quantize identically or not at all
    quantize = None
    if flags.get("quant", "") == "int8":
        from ..models.quant import quantize_params as quantize
        params = quantize(params)
        source += " (int8 weights)"
    print(f"serving: {source}", file=sys.stderr)

    from ..data.text import ByteTokenizer
    tokenizer = ByteTokenizer()
    eos = int(flags["eos"]) if flags.get("eos") else (
        hf_tok.eos_token_id if hf_tok is not None else None)
    spec_kwargs: dict = {}
    if flags.get("draft-model"):
        # speculative continuous batching — greedy or plain --temperature
        # sampling (DecodeServer rejects top-k/top-p); same flag family
        # as pst-generate
        from ..models.registry import get_model_and_batches as _get
        from ..models.transformer import Transformer as _T
        draft, _ = _get(flags["draft-model"], 1,
                        dtype=flags.get("dtype", ""))
        if not isinstance(draft, _T):
            raise ValueError(f"--draft-model={flags['draft-model']!r} "
                             "is not an LM")
        from .generate_main import draft_ckpt_flags, draft_cost_ratio
        dparams, dsource = load_params(
            draft_ckpt_flags(flags.get("draft-ckpt", ""),
                             flags.get("draft-lora-alpha", "")), draft,
            int(flags.get("draft-seed", int(flags.get("seed", 0)) + 1)),
            lora_flag="--draft-lora-alpha")
        dparams = match_layout(draft, dparams)
        print(f"draft: {dsource}", file=sys.stderr)
        spec_kwargs = dict(
            draft=draft, draft_params=dparams,
            draft_len=int(flags.get("draft-len", "4")),
            # adaptive depth on by default (--draft-len is the cap);
            # --no-adaptive-draft pins it.  --draft-cost-ratio overrides
            # the param-count proxy for the controller's cost model
            adaptive_draft="no-adaptive-draft" not in flags,
            draft_cost_ratio=draft_cost_ratio(flags, draft, model))
    follower = None
    if flags.get("follow"):
        # live weight publication (delta/, ISSUE 10): subscribe to a
        # training PS and hot-swap fresh weight versions between
        # admissions.  Every failure mode degrades to serving the
        # last-good weights — the decode process never crashes or stalls
        # on the training side's health (delta/subscriber.py).
        import os as _os

        from ..delta.subscriber import WeightFollower
        follower = WeightFollower(
            flags["follow"],
            subscriber_id=int(flags.get("subscriber-id",
                                        str(_os.getpid() & 0x7FFF))))
        follower.start()
        print(f"following weights from {flags['follow']}",
              file=sys.stderr)

    srv = DecodeServer(
        model, params,
        slots=int(flags.get("slots", "8")),
        max_len=int(flags.get("max-len", "2048")),
        temperature=float(flags.get("temperature", "0.0")),
        top_k=int(flags.get("top-k", "0")),
        top_p=float(flags.get("top-p", "0.0")),
        eos_id=eos,
        cache_dtype=("int8" if flags.get("kv-cache", "") == "int8"
                     else "native"),
        # --prompt-cache=N: repeated prompts skip the prefill forward
        # (LRU of N prompts' logits + K/V rows; 0 = off)
        prompt_cache=int(flags.get("prompt-cache", "0")),
        seed=int(flags.get("seed", 0)), **spec_kwargs)
    default_max_new = int(flags.get("default-max-new", "64"))

    if flags.get("serve-port") is not None or flags.get("coordinator"):
        # ---- decode fleet mode (fleet/, ISSUE 14): gRPC service +
        # coordinator registration instead of the line protocol.  The
        # line-protocol path below is byte-unchanged without these flags
        # (the downgrade matrix: no router => single-server pst-serve).
        import signal

        from ..fleet.decode import FleetDecodeServer
        fds = FleetDecodeServer(
            srv,
            server_id=int(flags.get("server-id", "0")),
            port=int(flags.get("serve-port", "0")),
            coordinator=flags.get("coordinator") or None,
            follower=follower, transform=quantize)
        port = fds.start()
        print(f"decode fleet server {fds.server_id} on port {port}"
              + (f", registered with {flags['coordinator']}"
                 if flags.get("coordinator") else " (standalone)"),
              file=sys.stderr)
        # graceful preemption: SIGTERM drains (in-flight streams finish,
        # then the server leaves the fleet) — the scale-in path
        signal.signal(signal.SIGTERM, lambda *_: fds.drain())
        try:
            while not fds.wait_drained(0.5):
                pass
        except KeyboardInterrupt:
            fds.drain()
            fds.wait_drained(10.0)
        fds.stop()
        print(f"serving stats: {json.dumps(srv.stats)}", file=sys.stderr)
        return 0

    in_q: "queue.Queue[dict | None]" = queue.Queue()
    threading.Thread(target=_reader, args=(in_q,), daemon=True,
                     name="pst-serve-stdin").start()

    pending: list[dict] = []          # parsed, awaiting a free slot
    fused_rounds = int(flags.get("fused-rounds", "1"))
    live: dict[int, dict] = {}        # request_id -> request (slot-held)
    text_mode: dict[int, bool] = {}
    eof = False

    def finish(req: dict, tokens: list[int], is_text: bool) -> None:
        done: dict = {"id": req.get("id"), "done": True}
        if is_text:
            # the terminator — global eos or a per-request stop token —
            # is metadata, not content: trim it from the decoded text
            # (admit() already rejected non-list "stop" fields)
            enders = {int(t) for t in req.get("stop") or ()}
            if eos is not None:
                enders.add(eos)
            cut = [i for i, t in enumerate(tokens) if t in enders]
            trim = tokens[:cut[0]] if cut else tokens
            done["text"] = (hf_tok.decode(trim) if hf_tok is not None
                            else tokenizer.decode(trim))
        else:
            done["tokens"] = tokens
        _emit(done)

    def finish_run() -> int:
        if follower is not None:
            follower.stop()
            if follower.degraded:
                print(f"weight follower degraded: "
                      f"{follower.degrade_reason} (kept serving version "
                      f"{follower.version})", file=sys.stderr)
        print(f"serving stats: {json.dumps(srv.stats)}", file=sys.stderr)
        return 0

    def maybe_swap() -> None:
        """Hot-swap the newest complete weight version (if any) between
        admissions.  A bad publication (shape/name drift after a model
        change upstream) must never kill serving — the server keeps the
        last-good weights and says so."""
        if follower is None:
            return
        fresh = follower.poll()
        if fresh is None:
            return
        store, version = fresh
        t0 = time.perf_counter()
        try:
            srv.swap_params(quantize(store) if quantize else store)
        except Exception as exc:  # noqa: BLE001 — serving boundary: keep
            # decoding on the last-good weights whatever the feed sends
            print(f"weight swap to version {version} failed ({exc}); "
                  f"keeping last-good weights", file=sys.stderr)
            return
        flight.record("publish.swap", a=version,
                      b=int(1e6 * (time.perf_counter() - t0)))
        print(f"weights: swapped to version {version}", file=sys.stderr)

    def admit() -> None:
        while pending and srv.has_free_slot:
            req = pending.pop(0)
            rid_key = req.get("id")
            try:
                if "tokens" in req:
                    ids = [int(t) for t in req["tokens"]]
                    is_text = False
                elif "prompt" in req:
                    if hf_tok is not None:
                        ids = hf_tok.encode(req["prompt"])
                    else:
                        from ..data.text import require_vocab
                        require_vocab(model.config.vocab, tokenizer)
                        ids = (tokenizer.encode(req["prompt"])
                               or [tokenizer.BOS])
                    is_text = True
                else:
                    raise ValueError("request needs 'prompt' or 'tokens'")
                temp = req.get("temperature")
                stop_field = req.get("stop", [])
                if not isinstance(stop_field, list):
                    # a JSON string would silently iterate per character
                    raise ValueError("'stop' must be an array of token ids")
                rid = srv.submit(
                    ids, int(req.get("max_new", default_max_new)),
                    temperature=None if temp is None else float(temp),
                    stop=[int(t) for t in stop_field])
            except Exception as exc:  # noqa: BLE001 — server boundary: a
                # malformed request (wrong types included) must become a
                # per-request error, never kill the other in-flight work
                _emit({"id": rid_key, "error": str(exc)})
                continue
            if rid in srv.finished():
                # max_new=1 (or instant EOS): the prefill token already
                # completed the request inside submit()
                tokens = srv.result(rid)
                for t in tokens:
                    _emit({"id": rid_key, "token": int(t)})
                finish(req, tokens, is_text)
                continue
            # the prefill forward already produced the first token —
            # stream it now (step() only emits subsequent ones)
            _emit({"id": rid_key, "token": int(srv.peek(rid)[0])})
            live[rid] = req
            text_mode[rid] = is_text

    while True:
        # drain whatever arrived on stdin without blocking the decode loop
        try:
            while True:
                item = in_q.get_nowait()
                if item is None:
                    eof = True
                    break
                tag, payload = item
                if tag == "err":
                    _emit({"error": payload})
                else:
                    pending.append(payload)
        except queue.Empty:
            pass
        # between admissions is the swap point: the next round dispatched
        # reads the fresh weights whole (the one step() left in flight
        # ran under the old ones; its tokens come with the next step())
        maybe_swap()
        admit()
        if srv.idle:
            if eof and not pending:
                return finish_run()
            if not pending:
                # nothing in flight: block for the next request (or EOF).
                # A following server wakes periodically so weight
                # versions keep swapping in while the queue is empty —
                # the first request after a quiet stretch must not be
                # served stale weights.
                try:
                    item = in_q.get(
                        timeout=0.5 if follower is not None else None)
                except queue.Empty:
                    maybe_swap()
                    continue
                if item is None:
                    return finish_run()
                tag, payload = item
                if tag == "err":
                    _emit({"error": payload})
                else:
                    pending.append(payload)
                continue
        # fuse rounds only when nothing is waiting for a slot — a
        # pending request must get the next admission opportunity
        emitted = (srv.step_many(fused_rounds)
                   if fused_rounds > 1 and not pending else srv.step())
        done_now = set(srv.finished())
        # stream every token BEFORE retiring finished requests: a
        # speculative round can emit several tokens for one rid, and the
        # finishing token may not be its last emitted pair
        for rid, token in emitted:
            _emit({"id": live[rid].get("id"), "token": int(token)})
        for rid in done_now & set(live):
            finish(live[rid], srv.result(rid), text_mode[rid])
            del live[rid], text_mode[rid]


if __name__ == "__main__":
    sys.exit(main())
