#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py              one TPU chip, Llama-3-8B width and depth
    python3 chip_smoke.py --chips 4    only the tp=4 path and what it is compared with
    python3 chip_smoke.py --rehearse   the same control flow on the CPU at tiny size

Drives the two entry points a user calls, the `dllama` CLI and the batched
`api_server`, on a checkpoint generated from --seed inside the run (no network,
no git), and checks what comes out by the repo's own means. Phases, each one
child process run after the other so the chip has one owner at a time:

    device  the start-up routine alone: which device does a process land on
    model   write a real-format .m/.t pair (examples/make_tiny_model.py --arch)
    cli     `dllama inference`, greedy, 32 steps; run twice, the second time
            the compiled programs must come back from the persistent cache
    parity  kernels against XLA dequant, teacher-forced logits at two depths:
            two layers under tight bounds that a mis-scaled matrix has to
            fail, then all of them under loose ones (apps/parity.py)
    serve   `api_server --batch 4 --superstep 8`: a plain completion, an SSE
            stream, four concurrent completions, the same greedy request twice
            along the same prefill path (identical bytes, no compile during
            the second), /v1/stats, then SIGTERM and a clean drain

Each phase prints one JSON line. The last line is exactly
`{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}`; it says
ok only if every phase passed on platform `tpu` with the expected chip count,
and the exit code is 0 only then. Off the chip the device phase fails and the
run stops there, unless --rehearse asks for the CPU explicitly (it can then
never end ok). This process is stdlib only and never imports JAX: a parent that
has touched JAX holds the chip, and a child that needs it then fails or hangs.

Timings it prints are first-run information, not a benchmark.
"""

import argparse
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, "chip_smoke_work")  # checkpoint; removed at the end
LOGS = os.path.join(ROOT, "chiprun_out", "chip_smoke")  # child logs; kept
ARCH = "llama3_8b"
STEPS = 32
SEQ_LEN = 2048
STARTUP_RE = re.compile(r"^🧭 startup (\{.*\})\s*$", re.M)
COMPILE_RE = re.compile(r"Finished XLA compilation|Compiling \S+ with global")


class Ctx:
    """What every phase needs: the arguments, the children's environment, the
    checkpoint paths once written, and every process still to be stopped."""

    def __init__(self, args):
        self.args = args
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = os.pathsep.join(
            p for p in (ROOT, os.environ.get("PYTHONPATH")) if p)
        self.env["PYTHONUNBUFFERED"] = "1"
        if args.rehearse:
            # the explicit request for the CPU, as many virtual devices as
            # chips, and Pallas interpret mode
            self.env["JAX_PLATFORMS"] = "cpu"
            self.env["DLT_PALLAS_INTERPRET"] = "1"
            self.env["XLA_FLAGS"] = (
                self.env.get("XLA_FLAGS", "") + " --xla_force_host_platform"
                f"_device_count={args.chips}").strip()
        self.model = self.tokenizer = None
        self.device = None
        self.procs: list[subprocess.Popen] = []

    def spawn(self, argv, log_name, env=None) -> subprocess.Popen:
        log = open(os.path.join(LOGS, log_name), "w")
        try:
            proc = subprocess.Popen(
                [sys.executable, *argv], cwd=ROOT, env=env or self.env,
                stdout=log, stderr=subprocess.STDOUT, start_new_session=True)
        finally:
            log.close()  # the child holds its own descriptor
        self.procs.append(proc)
        return proc

    def run(self, argv, log_name, timeout) -> tuple[int, str]:
        """Run one child to its end; (exit code, its output). 124 = killed at
        the time limit."""
        proc = self.spawn(argv, log_name)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            stop(proc)
            rc = 124
        with open(os.path.join(LOGS, log_name), errors="replace") as f:
            return rc, f.read()

    def stop_all(self) -> None:
        for proc in self.procs:
            stop(proc)


def stop(proc: subprocess.Popen) -> None:
    """Kill a child's whole process group and reap it."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait(timeout=30)


def tail(text: str, n: int = 12) -> str:
    return "\n".join(text.strip().splitlines()[-n:])


def last_json(text: str):
    for line in reversed(text.strip().splitlines()):
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    return None


def startup_block(text: str):
    m = STARTUP_RE.search(text)
    return json.loads(m.group(1)) if m else None


def check_device(ctx: Ctx, block, failures: list) -> None:
    """The contract's device: platform tpu, the chip count asked for."""
    if not block:
        failures.append("no start-up line")
    elif block["platform"] != "tpu":
        failures.append(f"device is {block['platform']}, not tpu")
    elif block["count"] != ctx.args.chips:
        failures.append(f"{block['count']} devices, expected {ctx.args.chips}")


# --------------------------------------------------------------------------
# phases: each returns the dict printed as its JSON line
# --------------------------------------------------------------------------

def phase_device(ctx: Ctx) -> dict:
    rc, out = ctx.run(
        ["-c", "from distributed_llama_tpu.platform_env import start; start()"],
        "device.log", timeout=300)
    failures = [] if rc == 0 else [f"exit code {rc}: {tail(out, 4)}"]
    block = startup_block(out) if rc == 0 else None
    check_device(ctx, block, failures)
    if block:
        ctx.device = {k: block[k] for k in ("platform", "kind", "count")}
    return {"phase": "device", "ok": not failures, "failures": failures,
            "startup": block}


def phase_model(ctx: Ctx) -> dict:
    argv = [os.path.join("examples", "make_tiny_model.py"), WORK,
            "--seed", str(ctx.args.seed)]
    if not ctx.args.rehearse:  # one size on the chip: the published one
        argv += ["--arch", ARCH]
    rc, out = ctx.run(argv, "model.log", timeout=600)
    made = last_json(out) if rc == 0 else None
    if not made:
        return {"phase": "model", "ok": False,
                "failures": [f"exit code {rc}: {tail(out, 4)}"]}
    ctx.model, ctx.tokenizer = made["model"], made["tokenizer"]
    return {"phase": "model", "ok": True, "failures": [], **made}


def _cli_once(ctx: Ctx, run: int) -> dict:
    rc, out = ctx.run(
        ["-m", "distributed_llama_tpu.apps.dllama", "inference",
         "--model", ctx.model, "--tokenizer", ctx.tokenizer,
         "--prompt", "The quick brown fox", "--steps", str(STEPS),
         "--temperature", "0", "--seed", str(ctx.args.seed),
         "--max-seq-len", str(SEQ_LEN)], f"cli{run}.log", timeout=900)
    failures = [] if rc == 0 else [f"exit code {rc}: {tail(out, 6)}"]
    res: dict = {"run": run, "failures": failures}
    if rc != 0:
        return res

    def grab(pattern, cast=float):
        m = re.search(pattern, out)
        return cast(m.group(1)) if m else None

    res["startup"] = startup_block(out)
    check_device(ctx, res["startup"], failures)
    res["load_s"] = grab(r"Loaded model in ([\d.]+)s")
    res["generated"] = grab(r"Generated tokens:\s+(\d+)", int)
    res["ms_per_token"] = grab(r"Avg inference time:\s+([\d.]+) ms")
    res["tokens_per_s"] = grab(r"Avg tokens / second:\s+([\d.]+)")
    res["prefill_ms"] = grab(r"Prefill time:\s+([\d.]+) ms")
    m = re.search(r"Compiled programs:\s+(\d+) in ([\d.]+) s \((\d+) from", out)
    if m:
        res["compile"] = {"programs": int(m.group(1)),
                          "seconds": float(m.group(2)),
                          "cache_hits": int(m.group(3))}
    else:
        failures.append("no compile line")
    m = re.search(r"Kernel selections:\s+(\{.*\})", out)
    res["kernel_selections"] = sel = json.loads(m.group(1)) if m else {}
    if res["generated"] != STEPS:
        failures.append(f"generated {res['generated']} tokens, not {STEPS}")
    if not res["ms_per_token"] or res["ms_per_token"] <= 0:
        failures.append("no decode timing")
    if "xla-fallback" in sel.values():
        failures.append("a matmul fell back to XLA")
    if "q4_matvec" not in sel.values():
        failures.append("the q4_matvec kernel did not engage")
    return res


def phase_cli(ctx: Ctx) -> dict:
    """Twice: the first run fills the persistent compile cache, the second has
    to read from it (compile seconds are set-up time either way)."""
    runs = [_cli_once(ctx, 1), _cli_once(ctx, 2)]
    failures = [f"run {r['run']}: {f}" for r in runs for f in r.pop("failures")]
    warm = runs[1].get("compile")
    if warm and not failures and warm["cache_hits"] == 0:
        # (the first run may find a warm cache too, where the machine comes
        # with one: both compile times are printed, only the hits are held)
        failures.append("second run read nothing from the compile cache")
    return {"phase": "cli", "ok": not failures, "failures": failures,
            "runs": runs}


def phase_parity(ctx: Ctx) -> dict:
    argv = ["-m", "distributed_llama_tpu.apps.parity", "--model", ctx.model,
            "--steps", "16", "--seed", str(ctx.args.seed),
            "--max-seq-len", str(SEQ_LEN)]
    if ctx.args.chips > 1:
        argv += ["--tp", str(ctx.args.chips)]
    rc, out = ctx.run(argv, "parity.log", timeout=900)
    res = last_json(out) or {}
    failures = []
    if rc != 0:
        failures.append(f"exit code {rc}" + ("" if res else f": {tail(out, 6)}"))
    if res:
        check_device(ctx, res.get("device"), failures)
        if not res.get("ok"):
            failures.append("a pass is out of its bounds, the canary went "
                            "unseen or an arm failed its own checks: `passes`")
    res.pop("ok", None)
    return {**res, "phase": "parity", "ok": not failures, "failures": failures}


def _http(port: int, path: str, body=None, timeout: float = 600.0):
    """(status, parsed JSON or raw text); status 0 when no reply came."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            status, raw = resp.status, resp.read()
    except urllib.error.HTTPError as e:
        status, raw = e.code, e.read()
    except OSError as e:  # refused, reset, timed out: status 0, the reason
        return 0, repr(e)
    text = raw.decode("utf-8", "replace")
    try:
        return status, json.loads(text)
    except ValueError:
        return status, text


def _chat(prompt: str, **kw) -> dict:
    return {"messages": [{"role": "user", "content": prompt}],
            "max_tokens": 16, "temperature": 0, **kw}


def _content(reply) -> str | None:
    try:
        return reply["choices"][0]["message"]["content"]
    except (KeyError, IndexError, TypeError):
        return None


def _prefill_path(port: int, reply) -> dict | None:
    """How the server admitted and prefilled a finished request, from its
    flight record: prompt tokens reused at admission (slot rewind + prefix
    cache) and the chunk sizes the rest was prefilled in."""
    rid = reply.get("id") if isinstance(reply, dict) else None
    status, rec = _http(port, f"/v1/requests/{rid}", timeout=60)
    if status != 200 or not isinstance(rec, dict):
        return None
    events = rec.get("events", [])
    admitted = next((e for e in events if e["event"] == "admitted"), None)
    if admitted is None:
        return None
    return {"reused": admitted["rewind_tokens"] + admitted["seeded_tokens"],
            "chunks": [e["chunk"] for e in events
                       if e["event"] == "prefill_chunk"]}


def phase_serve(ctx: Ctx) -> dict:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    argv = ["-m", "distributed_llama_tpu.apps.api_server",
            "--model", ctx.model, "--tokenizer", ctx.tokenizer,
            "--batch", "4", "--superstep", "8", "--host", "127.0.0.1",
            "--port", str(port), "--max-seq-len", str(SEQ_LEN)]
    if ctx.args.chips > 1:
        argv += ["--tp", str(ctx.args.chips)]
    log_path = os.path.join(LOGS, "serve.log")
    # every compile the server makes is logged, so the repeated request can
    # be held to "no compile"
    proc = ctx.spawn(argv, "serve.log", env=dict(ctx.env, JAX_LOG_COMPILES="1"))
    failures: list = []
    res: dict = {"phase": "serve"}

    def log_text() -> str:
        with open(log_path, errors="replace") as f:
            return f.read()

    try:
        t0 = time.monotonic()
        health = None
        while time.monotonic() - t0 < 600:
            if proc.poll() is not None:
                failures.append(f"server exited with {proc.returncode} during "
                                f"start-up: {tail(log_text(), 6)}")
                return {**res, "ok": False, "failures": failures}
            status, health = _http(port, "/healthz", timeout=5)
            if status == 200:
                break
            time.sleep(1.0)
        else:
            failures.append("server never became healthy")
            return {**res, "ok": False, "failures": failures}
        res["ready_s"] = round(time.monotonic() - t0, 1)
        check_device(ctx, health.get("device"), failures)

        def timed(label, fn):
            t = time.monotonic()
            out = fn()
            res.setdefault("seconds", {})[label] = round(
                time.monotonic() - t, 2)
            return out

        # 1. one plain completion (pays the first compiles)
        status, reply = timed("plain", lambda: _http(
            port, "/v1/chat/completions", _chat("Say hello.")))
        if status != 200 or _content(reply) is None:
            failures.append(f"plain completion: HTTP {status} {reply!r:.200}")

        # 2. one SSE stream
        def stream():
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/chat/completions",
                data=json.dumps(_chat("Count to five.", stream=True)).encode(),
                headers={"Content-Type": "application/json"})
            events, done = 0, False
            with urllib.request.urlopen(req, timeout=600) as resp:
                for raw in resp:
                    line = raw.decode("utf-8", "replace").strip()
                    if line == "data: [DONE]":
                        done = True
                    elif line.startswith("data: "):
                        json.loads(line[6:])
                        events += 1
            return events, done

        try:
            events, done = timed("stream", stream)
            res["stream_events"] = events
            if not done or events == 0:
                failures.append(f"SSE stream: {events} events, done={done}")
        except (OSError, ValueError) as e:
            failures.append(f"SSE stream: {e!r}")

        # 3. four concurrent completions, one per slot
        replies: list = [None] * 4

        def one(i):
            replies[i] = _http(port, "/v1/chat/completions",
                               _chat(f"Question number {i}: why?"))

        def four():
            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=900)
            return [t.is_alive() for t in threads]

        if any(timed("concurrent4", four)):
            failures.append("a concurrent completion never returned")
        for i, r in enumerate(replies):
            if not r or r[0] != 200 or _content(r[1]) is None:
                failures.append(f"concurrent completion {i}: {r!r:.200}")

        # 4. the same greedy request twice: identical bytes, and the second
        # time nothing compiles. Identity holds between requests that run the
        # same programs on the same inputs, and the slot's prefix reuse
        # decides which programs: a first send prefills its prompt in chunks
        # of 64/8/1, a repeat rewinds to the last prompt token and prefills
        # that one alone, and in bf16 on flat random logits the two may
        # round to different tokens. So the request is sent once to prime
        # the slot, and the pair that is compared must show the same prefill
        # path in the server's flight record. The prompt is cut so that the
        # priming send ends on a chunk of 8: `prime_identical` then reports,
        # as information, whether the other path changed a token here.
        body = _chat("Repeat after me: the chip is up now.")

        def send(label):
            status, reply = timed(label, lambda: _http(
                port, "/v1/chat/completions", body))
            return status, _content(reply), _prefill_path(port, reply)

        sends = [send("prime"), send("repeat1")]
        mark = len(log_text())
        sends.append(send("repeat2"))
        compiled = COMPILE_RE.findall(log_text()[mark:])
        (_, prime, prime_path), (_, a, path_a), (_, b, path_b) = sends
        res["repeat"] = {"bytes": None if a is None else len(a.encode()),
                         "path": path_a, "prime_path": prime_path,
                         "prime_identical": prime is not None and prime == a}
        if any(status != 200 for status, _, _ in sends) or a is None or a != b:
            failures.append(f"repeated request differs: {a!r:.80} / {b!r:.80}")
        if path_a is None or path_a != path_b:
            failures.append("the repeated requests took different prefill "
                            f"paths: {path_a} / {path_b}")
        if compiled:
            failures.append(f"{len(compiled)} compile log lines during the "
                            "repeated request")

        # 5. what the server says about itself
        status, stats = _http(port, "/v1/stats", timeout=60)
        if status != 200 or not isinstance(stats, dict):
            failures.append(f"/v1/stats: HTTP {status}")
        else:
            check_device(ctx, stats.get("device"), failures)
            kernels = stats.get("kernels", {})
            be = stats.get("batch_engine", {})
            res.update(device=stats.get("device"), compile=stats.get("compile"),
                       kernels=kernels,
                       batch_engine={k: be.get(k) for k in (
                           "slots", "superstep", "super_steps", "decode_steps",
                           "prefilled_tokens")},
                       paged_kv="paged_kv" in be)
            if "xla-fallback" in kernels.get("selections", {}).values():
                failures.append("a matmul fell back to XLA")
            if not kernels.get("paged_kernel"):
                failures.append("the paged-attention kernel did not engage")
            if "paged_kv" not in be:
                failures.append("paged KV is off")
            if not be.get("super_steps"):
                failures.append("no super-step ran")
            tpot = stats.get("metrics", {}).get("api_request_tpot_seconds")
            if isinstance(tpot, dict) and tpot.get("count"):
                res["tpot_ms_mean"] = round(
                    1e3 * tpot["sum"] / tpot["count"], 2)

        # 6. SIGTERM: drain and leave with exit code 0
        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            rc = None
        res["exit_code"] = rc
        if rc != 0:
            failures.append(f"after SIGTERM the server exit code was {rc}")
        if "drained, server stopped" not in log_text():
            failures.append("no clean drain in the server's log")
    finally:
        stop(proc)
    failures = list(dict.fromkeys(failures))  # /healthz and /v1/stats agree
    return {**res, "ok": not failures, "failures": failures}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the tp=4 path and what it is compared "
                         "with (parity tp=1 against tp=4, then serve --tp 4)")
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--rehearse", action="store_true",
                    help="explicit CPU + Pallas interpret request at tiny "
                         "size: exercises every phase, can never end ok")
    args = ap.parse_args(argv)

    os.makedirs(LOGS, exist_ok=True)
    ctx = Ctx(args)
    phases = ([phase_device, phase_model, phase_parity, phase_serve]
              if args.chips > 1 else
              [phase_device, phase_model, phase_cli, phase_parity, phase_serve])
    ok = True
    try:
        for phase in phases:
            t0 = time.monotonic()
            result = phase(ctx)
            result["seconds_total"] = round(time.monotonic() - t0, 1)
            print(json.dumps(result), flush=True)
            ok = ok and result["ok"]
            # without a device there is nothing to drive (unless the CPU was
            # asked for), and without a checkpoint nothing to load
            if not result["ok"] and (
                    phase is phase_model
                    or phase is phase_device and not args.rehearse):
                break
    finally:
        ctx.stop_all()
        shutil.rmtree(WORK, ignore_errors=True)
    print(json.dumps({"ok": ok, "device": ctx.device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
