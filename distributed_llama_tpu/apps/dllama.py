"""dllama CLI — benchmark / generate / chat modes.

TPU-native counterpart of src/apps/dllama/dllama.cpp. The reference's `worker` mode
(dllama.cpp:205-221) has no equivalent: worker processes are replaced by SPMD shards of
one program, so a "worker" is just a mesh device. `--workers host:port` becomes `--tp N`;
`--nthreads` is meaningless (XLA owns the chip) and accepted-but-ignored for CLI
compatibility.

Modes (dllama.cpp:230-245):
    inference  — run prompt + --steps tokens, print per-token G/I/T-style stats
    generate   — stream tokens until EOS or --steps
    chat       — interactive REPL with chat template + stop detection (dllama.cpp:111-194)
"""

from __future__ import annotations

import argparse
import json
import sys

from ..models.spec import ModelSpec
from ..quants import FloatType
from ..runtime.engine import Engine
from ..runtime.sampler import Sampler
from ..tokenizer import ChatItem, ChatTemplate, EosDetector, TemplateType
from ..tokenizer.eos import TokenStreamer


def build_parser(include_mode: bool = True) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="dllama", description=__doc__)
    if include_mode:
        p.add_argument("mode", choices=["inference", "generate", "chat"])
    p.add_argument("--model", required=True)
    p.add_argument("--tokenizer", required=True)
    p.add_argument("--prompt", default=None)
    p.add_argument("--steps", type=int, default=64)
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--topp", type=float, default=0.9)
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--chat-template", default=None,
                   choices=[t.value for t in TemplateType])
    p.add_argument("--max-seq-len", type=int, default=0)
    p.add_argument("--weights-float-type", default=None,
                   choices=["f32", "f16", "q40", "q80"])
    p.add_argument("--buffer-float-type", default="q80",
                   choices=["f32", "f16", "q40", "q80"],
                   help="q80 enables int8-compressed collectives (the reference's "
                        "wire compression, tasks.cpp:96-135). Numerics are pinned by "
                        "tests and perf/microbench.py --section collectives; its TIME "
                        "on real multi-chip ICI is UNMEASURED (no multi-chip hardware "
                        "available) — expected to matter across DCN, likely a wash "
                        "on ICI")
    p.add_argument("--tp", type=int, default=None, help="tensor-parallel devices")
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel devices (ring attention over the KV cache)")
    p.add_argument("--pod", action="store_true",
                   help="join a multi-host pod job via jax.distributed and mesh over "
                        "every chip in the job — the SPMD replacement for the "
                        "reference's `dllama worker` + --workers bootstrap "
                        "(dllama.cpp:205-221). On Cloud TPU the coordinator is "
                        "auto-discovered; elsewhere pass --coordinator/--num-processes/"
                        "--process-id. Run the SAME command on every host.")
    p.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                   help="jax.distributed coordinator for --pod off Cloud TPU")
    p.add_argument("--num-processes", type=int, default=None,
                   help="total processes in the --pod job (off Cloud TPU)")
    p.add_argument("--process-id", type=int, default=None,
                   help="this process's index in the --pod job (off Cloud TPU)")
    p.add_argument("--dtype", default="auto", choices=["auto", "float32", "bfloat16"],
                   help="auto = bfloat16 on TPU, float32 on CPU")
    p.add_argument("--no-pallas", action="store_true")
    p.add_argument("--moe-sharding", default="slice", choices=["slice", "expert"],
                   help="MoE expert placement over the tp axis: 'slice' TP-slices "
                        "every expert's hidden dim (the reference's scheme); "
                        "'expert' shards WHOLE experts (each chip owns E/tp experts "
                        "— the capacity axis for Grok-1-314B-class expert weights; "
                        "requires n_experts %% tp == 0)")
    p.add_argument("--pipeline", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="pipelined super-steps for batched serving (--batch "
                        "> 1, api_server/bench): eagerly chain decode "
                        "dispatch N+1 from device-resident state (last "
                        "token, positions, xorshift* RNG) while N's token "
                        "block transfers and is delivered host-side, so the "
                        "device never idles through EOS scans and callbacks; "
                        "output stays token-identical (a diverging block "
                        "flushes the in-flight dispatch). --no-pipeline "
                        "restores the serialized host<->device loop "
                        "(docs/SERVING.md \"Pipelined decode\")")
    p.add_argument("--device-loop", type=int, default=0, metavar="CHUNK",
                   help="decode CHUNK tokens per dispatch with the on-device scan loop "
                        "(runtime/device_loop.py); 0 = per-token host loop")
    p.add_argument("--speculative", type=int, default=0, metavar="K",
                   help="prompt-lookup speculative decoding: draft up to K "
                        "tokens from context n-gram matches and verify them "
                        "in one step. Sequential mode (--batch 1, "
                        "runtime/speculative.py) is greedy-only; with the "
                        "api_server's --batch > 1 the BatchEngine verifies "
                        "per-row draft blocks in one batched dispatch — "
                        "greedy AND seeded-stochastic, token-identical "
                        "either way (docs/SERVING.md \"Speculative "
                        "decoding\"). No reference counterpart")
    p.add_argument("--draft-model", default=None, metavar="PATH",
                   help="model-based speculative drafting (api_server "
                        "--batch > 1 only): load a second, small model from "
                        "PATH (same .m format/loaders as --model, vocab "
                        "must match), co-resident on the target's mesh, "
                        "drafting k tokens per row in one scan dispatch "
                        "with ADAPTIVE per-row k; n-gram lookup remains "
                        "the per-row fallback (docs/SERVING.md "
                        "\"Model-based drafting\"). Implies --speculative 8 "
                        "when K is unset")
    p.add_argument("--draft-k", type=int, default=0, metavar="K",
                   help="cap the model drafter's per-row draft length "
                        "(default: the --speculative K). The adaptive "
                        "controller picks each row's k from the bucketed "
                        "range [0, K]")
    p.add_argument("--trace", default=None, metavar="OUT.json",
                   help="record runtime spans (prefill chunks, decode "
                        "dispatches, super-steps, cold-attention callbacks) "
                        "and write a Chrome trace-event JSON at exit — load "
                        "it in Perfetto (ui.perfetto.dev) or chrome://tracing "
                        "(obs/trace.py; docs/OBSERVABILITY.md)")
    p.add_argument("--nthreads", type=int, default=None, help="ignored (XLA owns the chip)")
    p.add_argument("--kv-cache-storage", default=None,
                   choices=["ram", "host", "disc"],
                   help="'ram' (default): KV cache in HBM. 'host'/'disc': paged "
                        "out-of-core cache (runtime/paged_cache.py) — a device "
                        "hot ring of --kv-cache-resident recent positions plus "
                        "the full history in host RAM / an mmap'd disk file "
                        "pair (the reference's disc cache, transformer.cpp:"
                        "312-318, rebuilt flash-attention-style). Capacity "
                        "valve: exact attention over the whole context at "
                        "host-bandwidth speed; use --sp to go FAST instead")
    p.add_argument("--kv-cache-resident", type=int, default=1024, metavar="R",
                   help="paged mode: positions kept HBM-resident (rounded up "
                        "to a multiple of 64)")
    p.add_argument("--kv-cache-dir", default=None, metavar="DIR",
                   help="paged 'disc' mode: directory for the key/value cache "
                        "files (default: a fresh temp dir)")
    return p


def check_kv_storage(args) -> None:
    """The reference's `--kv-cache-storage disc` spills the KV cache to mmap'd disk
    files (src/transformer.cpp:312-318, utils.cpp:50-67) — an out-of-core valve for
    small-RAM CPU nodes. The paged cache (runtime/paged_cache.py) is the TPU-native
    equivalent: hot ring in HBM, full history on host/disk, exact merged attention.
    State the cost up front — every decoded token re-reads the cold history from
    host memory, so throughput falls with context length; --sp (ring attention over
    ICI) is the FAST long-context path when more chips are available."""
    if args.kv_cache_storage in ("host", "disc"):
        print(f"💡 paged KV cache ({args.kv_cache_storage}): hot ring of "
              f"{args.kv_cache_resident} positions in HBM, full history "
              f"{'on disk (mmap)' if args.kv_cache_storage == 'disc' else 'in host RAM'}."
              " Decode slows as the cold history grows; prefer --sp N when "
              "more chips are available (README §long-context).",
              file=sys.stderr)


_FT = {"f32": FloatType.F32, "f16": FloatType.F16, "q40": FloatType.Q40,
       "q80": FloatType.Q80}

_GRACEFUL_STOP = None  # threading.Event set by the first SIGTERM


def install_graceful_stop():
    """SIGTERM during a CLI generation stops cleanly after the current token
    (stats still print, the partial output is complete text) instead of
    killing the process mid-dispatch; a second SIGTERM hard-stops via
    KeyboardInterrupt. Returns the Event, or None where signal handlers
    can't be installed (non-main thread, e.g. under a test runner)."""
    global _GRACEFUL_STOP
    import signal
    import threading

    ev = threading.Event()

    def _on_term(signum, frame):
        if ev.is_set():
            raise KeyboardInterrupt
        ev.set()
        print("\n🟡 SIGTERM: finishing the current token, then stopping "
              "(send again to hard-stop)", file=sys.stderr)

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except ValueError:  # not the main thread
        return None
    _GRACEFUL_STOP = ev
    return ev


def stop_requested() -> bool:
    """True once SIGTERM asked the CLI generation loop to wind down."""
    return _GRACEFUL_STOP is not None and _GRACEFUL_STOP.is_set()


def install_trace(args) -> bool:
    """--trace bootstrap (shared by dllama and api_server): install the
    process-wide tracer before any engine work so model-load/compile spans
    are captured too. Returns True when tracing is on."""
    if not getattr(args, "trace", None):
        return False
    from ..obs import trace

    trace.install()
    return True


def dump_trace(args) -> None:
    """Write the Chrome trace to args.trace (no-op when --trace is unset)."""
    from ..obs import trace

    t = trace.current()
    if getattr(args, "trace", None) and t is not None:
        t.dump(args.trace)
        n = len(t.events())
        print(f"🧭 wrote {n} trace events to {args.trace} "
              f"({t.dropped_events} dropped) — open in ui.perfetto.dev",
              file=sys.stderr)


def init_pod(args) -> int:
    """--pod bootstrap: join the jax.distributed job before any device use.
    Returns this host's process index (0 when not a pod job)."""
    if not getattr(args, "pod", False):
        return 0
    from ..parallel.mesh import init_multihost

    idx = init_multihost(coordinator=args.coordinator,
                         num_processes=args.num_processes,
                         process_id=args.process_id)
    import jax

    print(f"🌐 Pod process {idx}/{jax.process_count()}: "
          f"{jax.local_device_count()} local / {jax.device_count()} global chips")
    return idx


def policy_kwargs(args) -> dict:
    """The dtype / kernel choice the flags ask for (None = the backend
    decides, platform_env.resolve_kernel_policy) — one reading for the
    start-up line and for every engine an entry point builds."""
    import jax.numpy as jnp

    return dict(
        dtype=(None if args.dtype == "auto"
               else jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32),
        use_pallas=False if args.no_pallas else None)


def startup(args) -> None:
    """First act of the dllama and api_server mains: join the pod job (it
    must precede backend initialization), then place the compile cache and
    print the one start-up line (platform_env.start)."""
    from ..platform_env import start

    init_pod(args)
    start(**policy_kwargs(args))


def make_engine(args) -> Engine:
    import time

    t0 = time.perf_counter()
    engine = Engine.load(
        args.model, args.tokenizer, max_seq_len=args.max_seq_len,
        weights_ftype=_FT[args.weights_float_type] if args.weights_float_type else None,
        tp=args.tp, sp=args.sp, pod=getattr(args, "pod", False),
        **policy_kwargs(args),
        compress_collectives=args.buffer_float_type == "q80" and (args.tp or 1) > 1,
        moe_sharding=args.moe_sharding,
        kv_cache_storage=args.kv_cache_storage,
        kv_cache_resident=args.kv_cache_resident,
        kv_cache_dir=args.kv_cache_dir,
    )
    print(f"⏩ Loaded model in {time.perf_counter() - t0:.1f}s "
          f"(tp={engine.tp}, pallas={engine.use_pallas})")
    spec = engine.spec
    for k in ("dim", "hidden_dim", "n_layers", "n_heads", "n_kv_heads", "vocab_size",
              "seq_len"):
        print(f"💡 {k}: {getattr(spec, k)}")
    return engine


def make_sampler(args, spec: ModelSpec) -> Sampler:
    import time

    seed = args.seed if args.seed is not None else int(time.time())
    return Sampler(spec.vocab_size, args.temperature, args.topp, seed)


def mode_inference(args) -> None:
    engine = make_engine(args)
    sampler = make_sampler(args, engine.spec)
    tok = engine.tokenizer
    prompt = tok.encode(args.prompt or "Hello world", add_bos=True)
    pieces: list[bytes] = []
    if engine.tp > 1 or engine.sp > 1:
        # account the compiled step's actual collectives so the S/R columns are
        # measured (the reference counted socket bytes; dllama.cpp:76-93)
        mt = engine.collective_stats()
        counts = " ".join(f"{k}x{v}" for k, v in sorted(mt.counts.items()))
        print(f"🔷 Collectives/step: {counts} "
              f"({mt.total_payload_bytes / 1024:.0f} kB payload)")

    def on_token(t):
        piece = tok.decode_piece(prompt[-1] if not pieces else 0, t)
        pieces.append(piece)

    out, stats = engine.generate_with(prompt, args.steps, sampler, on_token=on_token,
                                      stop_check=lambda t: stop_requested(),
                                      device_loop_chunk=args.device_loop,
                         speculative_k=args.speculative)
    text = b"".join(pieces).decode("utf-8", errors="replace")
    print(text)
    # per-token stats table like dllama.cpp:76-93. The reference's columns are G(total),
    # I(inference), T(root socket transfer) (utils.cpp:215-218). Here I = the on-device
    # step through the logits' arrival on the host, which the sampler needs anyway;
    # ICI collective time is fused into the compiled step and cannot be split out at
    # runtime, so the third column is H = host sampling/bookkeeping ms — labeled as
    # what it is rather than printed as "transfer".
    for i, (g, inf) in enumerate(zip(stats.token_ms, stats.infer_ms)):
        print(f"🔶 G {g:7.2f} ms I {inf:7.2f} ms H {g - inf:7.2f} ms "
              f"S {stats.sent_kbytes_per_token:8.0f} kB R {stats.recv_kbytes_per_token:8.0f} kB {pieces[i].decode('utf-8', 'replace')}")
    print("Columns: G total/token, I device step (incl. logits copy), H host sampling;")
    print(f"S/R source:          {stats.traffic_source} per-device ring bytes")
    print(f"Generated tokens:    {stats.generated_tokens}")
    print(f"Avg tokens / second: {stats.tokens_per_second:.2f}")
    print(f"Avg generation time: {stats.avg_token_ms:.2f} ms")
    print(f"Avg inference time:  {stats.avg_infer_ms:.2f} ms")
    if stats.avg_infer_ms > 0:
        gbps = engine.decode_weight_bytes / engine.tp / 1e9 / (stats.avg_infer_ms / 1e3)
        print(f"Weight stream:       {gbps:.1f} GB/s per chip "
              f"({engine.decode_weight_bytes / 1e9:.3f} GB/step global)")
    print(f"Prefill time:        {stats.prefill_ms:.2f} ms "
          f"({stats.prompt_tokens} tokens)")
    from ..ops.matmul import kernel_selections
    from ..platform_env import xla_compiles

    # compile time is set-up, not speed: the timings above include it in the
    # first dispatch of each shape (prefill chunks, the first decode token)
    c = xla_compiles.snapshot()
    print(f"Compiled programs:   {c['programs']} in {c['seconds']:.1f} s "
          f"({c['cache_hits']} from the persistent cache)")
    print(f"Kernel selections:   "
          f"{json.dumps(kernel_selections(), sort_keys=True)}")
    if getattr(stats, "spec_steps", 0):
        # speculative decoding: dispatches vs tokens is the whole story
        acc = stats.spec_accepted / max(stats.spec_drafted, 1)
        print(f"Speculative:         {stats.generated_tokens} tokens in "
              f"{stats.spec_steps} verify steps "
              f"({stats.spec_accepted}/{stats.spec_drafted} drafts accepted, "
              f"{acc:.0%})")


def mode_generate(args) -> None:
    engine = make_engine(args)
    sampler = make_sampler(args, engine.spec)
    tok = engine.tokenizer
    prompt = tok.encode(args.prompt or "", add_bos=True)
    prev = prompt[-1] if prompt else -1

    def on_token(t):
        nonlocal prev
        sys.stdout.buffer.write(tok.decode_piece(prev, t))
        sys.stdout.flush()
        prev = t

    engine.generate_with(prompt, args.steps, sampler, on_token=on_token,
                         stop_check=lambda t: t == tok.eos_id or stop_requested(),
                         device_loop_chunk=args.device_loop,
                         speculative_k=args.speculative)
    print()


def mode_chat(args) -> None:
    """Interactive REPL (Chat::chat, dllama.cpp:132-193): KV position persists across
    turns; generation stops on chat EOS or stop strings."""
    engine = make_engine(args)
    sampler = make_sampler(args, engine.spec)
    tok = engine.tokenizer
    template = ChatTemplate(args.chat_template or TemplateType.UNKNOWN,
                            tok.chat_template, tok.eos_piece())
    stops = tok.chat_stops()

    print("💻 System prompt (optional): ", end="", flush=True)
    system = sys.stdin.readline().strip()
    first = True
    while True:
        print("\n👱 User\n> ", end="", flush=True)
        user = sys.stdin.readline()
        if not user:
            break
        items = []
        if first and system:
            items.append(ChatItem("system", system))
        items.append(ChatItem("user", user.strip()))
        rendered = template.generate(items)
        prompt = tok.encode(rendered, add_bos=first)
        if engine.pos + len(prompt) >= engine.spec.seq_len:
            # next turn's prompt no longer fits the KV cache: hard stop at context
            # end like the reference (dllama.cpp:190-192) instead of overflowing
            print("\n(context end reached)")
            break
        first = False

        print("\n🤖 Assistant\n", flush=True)
        detector = EosDetector(tok.chat_eos_id, stops,
                               padding_left=2, padding_right=2)

        def emit(delta: bytes):
            sys.stdout.buffer.write(delta)
            sys.stdout.flush()

        streamer = TokenStreamer(detector, lambda t: tok.decode_piece(0, t), emit)
        engine.generate_with(prompt, engine.spec.seq_len - engine.pos - 1, sampler,
                             on_token=streamer.on_token,
                             stop_check=lambda t: (streamer.stop_check(t)
                                                   or stop_requested()),
                             device_loop_chunk=args.device_loop,
                         speculative_k=args.speculative)
        if stop_requested():
            print("\n(terminated)")
            break
        if engine.pos >= engine.spec.seq_len - 1:
            print("\n(context end reached)")
            break


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    startup(args)
    if args.draft_model:
        import sys

        print("⚠️  --draft-model needs the batched verify path — it is an "
              "api_server --batch > 1 feature; the sequential CLI keeps "
              "prompt-lookup drafting (--speculative).", file=sys.stderr)
    check_kv_storage(args)
    install_trace(args)
    from ..resilience import faults

    faults.install_from_env()  # DLLAMA_FAULTS chaos config (resilience/)
    install_graceful_stop()  # SIGTERM: stop after the current token
    try:
        {"inference": mode_inference, "generate": mode_generate,
         "chat": mode_chat}[args.mode](args)
    finally:
        dump_trace(args)


if __name__ == "__main__":
    main()
