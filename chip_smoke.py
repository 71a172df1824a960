#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives the main path once through the entry points a user calls, at the
1.2B width of ``configs/lm_1p2b.yml`` (hidden 2048, 16 layers, 16 heads,
mlp 8192, vocab 32768, bf16; random weights from a seed):

    python chip_smoke.py            # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4  # the path across four chips, nothing else

One chip, three child processes one after the other, each the only
holder of the chip:

1. ``python -m mlcomp_tpu.cli dag configs/lm_1p2b.yml`` — the train
   stage takes four optimizer steps at B=2 x S=4096 (Adafactor, no
   remat, flash forward+backward kernels) and stores a checkpoint;
2. ``python -m mlcomp_tpu.cli serve --model configs/lm_1p2b.yml
   --storage-task lm/lm_1p2b/train --quantize kernel --warmup`` — the
   default continuous batcher with dense int8 KV and int8 weights
   answers ``POST /generate`` over HTTP, and ``GET /profile`` must show
   device time under the flash, decode-attention and int8-matmul kernels;
3. the same daemon with ``--kv-layout paged``: same prompts, greedy ids
   equal to the dense daemon's.

``--chips 4`` runs only what exists only across chips: the train stage
of ``configs/lm_1p2b_mesh.yml`` (SPMD over dp=2 x tp=2) against the same
steps on a one-device mesh (``tools/mesh_train_check.py``), then
``cli fleet --replicas 4 --chips 1`` on the stored checkpoint, each
replica on its own chip behind the router.

This process never imports JAX: a parent that has touched JAX holds the
chip and its children then fail or hang.  It learns the device from the
children (the train executor's start line, the daemons' ``/healthz``).

Output: one JSON object per phase, then — only if every phase passed —
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
as the last line, exit code 0.  Any failure: exit code 1 and no such
line.  This is a smoke test, not a measurement: it prints seconds spent
(compile seconds where a child reports them) but no rate.

Everything a run needs it makes itself, from committed files and a seed,
under ``.chip_smoke/`` (wiped at start; listed in .gitignore); the
persistent compile cache is where ``JAX_COMPILATION_CACHE_DIR`` says or
``.jax_cache/`` (mlcomp_tpu/utils/compile_cache.py).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import re
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent
RUN = ROOT / ".chip_smoke"
CONFIG = ROOT / "configs" / "lm_1p2b.yml"
MESH_CONFIG = ROOT / "configs" / "lm_1p2b_mesh.yml"
PLATFORM = "tpu"          # anything else is a failure before any phase
SEED = 0
BUDGET_S = 1150.0         # the driver allows 1200 s, compilation included
PORT = 18900              # serve daemon / fleet router; replicas follow

# the one serving geometry every daemon of a run is given, so --warmup
# compiles a handful of 1.2B programs and the paged daemon reuses the
# dense daemon's prefill programs from the compile cache: prompts up to
# 2048 tokens + 64 new = the 2304-slot int8 KV buffer (128-token pages
# tile its 384-slot kernel blocks); K pinned so one dispatch program
# family is built instead of the adaptive 1/2/4/8 ladder
SERVE_ARGS = [
    "--quantize", "kernel", "--warmup",
    "--prompt-buckets", "2048", "--batch-sizes", "4",
    "--max-new-buckets", "64", "--steps-per-dispatch", "4",
]
PAGED_ARGS = ["--kv-layout", "paged", "--kv-page-tokens", "128",
              "--max-slots", "4"]
PROMPT_LENS = (1500, 2048, 300)
NEW_TOKENS = 24
KERNELS = ("flash", "decode_attention", "quant_matmul")

_T0 = time.monotonic()
_children: list = []


class SmokeFailure(Exception):
    pass


def remaining() -> float:
    return BUDGET_S - (time.monotonic() - _T0)


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


# ------------------------------------------------------------- children


def child_env(extra=None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(ROOT), env.get("PYTHONPATH")) if p
    )
    env["MLCOMP_TPU_STORAGE"] = str(RUN / "storage")
    env["PYTHONUNBUFFERED"] = "1"
    env.update(extra or {})
    return env


def spawn(argv, log_name: str, env=None) -> subprocess.Popen:
    """Start a child in its own process group with its output in
    ``.chip_smoke/<log_name>``; registered for the kill-on-exit sweep."""
    log = open(RUN / log_name, "wb")
    try:
        proc = subprocess.Popen(
            # faulthandler: a child killed for hanging (abort_hung)
            # leaves every thread's Python stack in its log
            [sys.executable, "-X", "faulthandler", *argv],
            cwd=str(ROOT), env=child_env(env),
            stdout=log, stderr=subprocess.STDOUT, start_new_session=True,
        )
    finally:
        log.close()
    proc.log_path = RUN / log_name
    _children.append(proc)
    return proc


def kill(proc: subprocess.Popen, grace: float = 15.0) -> None:
    """SIGINT the child (``cli fleet`` stops its replicas — each in a
    session of its own — only on that path), then SIGTERM its whole
    process group, SIGKILL what is left."""
    if proc.poll() is None:
        try:
            proc.send_signal(signal.SIGINT)
            proc.wait(timeout=grace)
        except (ProcessLookupError, subprocess.TimeoutExpired):
            pass
    for sig, wait in ((signal.SIGTERM, grace), (signal.SIGKILL, 5.0)):
        try:
            os.killpg(proc.pid, sig)
        except (ProcessLookupError, PermissionError):
            break
        try:
            proc.wait(timeout=wait)
        except subprocess.TimeoutExpired:
            continue
    if proc in _children:
        _children.remove(proc)


def abort_hung(proc: subprocess.Popen) -> None:
    """A child that overran its time limit: SIGABRT first, so
    faulthandler writes where every thread was, then the group dies."""
    try:
        os.kill(proc.pid, signal.SIGABRT)
        proc.wait(timeout=10.0)
    except (ProcessLookupError, subprocess.TimeoutExpired):
        pass
    kill(proc, grace=1.0)


def kill_all() -> None:
    for proc in list(_children):
        kill(proc, grace=5.0)


def log_tail(proc, n: int = 30) -> str:
    try:
        lines = Path(proc.log_path).read_text(errors="replace").splitlines()
    except OSError:
        return ""
    return "\n".join(lines[-n:])


def run_to_end(argv, log_name: str, timeout: float, env=None) -> str:
    """Run a child to completion; returns its output.  Non-zero exit or
    timeout is a failure carrying the end of its log."""
    proc = spawn(argv, log_name, env=env)
    try:
        rc = proc.wait(timeout=max(1.0, min(timeout, remaining())))
    except subprocess.TimeoutExpired:
        abort_hung(proc)
        raise SmokeFailure(
            f"{log_name}: no exit within its time limit\n"
            f"{log_tail(proc, 80)}"
        )
    kill(proc, grace=1.0)   # sweep anything it left in its group
    if rc != 0:
        raise SmokeFailure(f"{log_name}: exit code {rc}\n{log_tail(proc)}")
    return Path(proc.log_path).read_text(errors="replace")


def last_json_line(text: str, key: str) -> dict:
    for line in reversed(text.splitlines()):
        line = line.strip()
        if line.startswith("{") and f'"{key}"' in line:
            try:
                return json.loads(line)
            except ValueError:
                continue
    raise SmokeFailure(f"no JSON line with {key!r} in child output")


# ----------------------------------------------------------------- HTTP


def http_json(url: str, body=None, timeout: float = 120.0):
    """GET (body None) or POST JSON; returns (payload, headers)."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        # an answer, but the wrong one: never retried, always fatal
        raise SmokeFailure(
            f"{url}: HTTP {e.code}: {e.read()[:1000].decode('utf-8', 'replace')}"
        )


def wait_ready(proc, url: str, timeout: float, ready) -> dict:
    """Poll ``url`` until ``ready(payload)``; fails when the child dies
    or the time limit passes."""
    deadline = time.monotonic() + max(1.0, min(timeout, remaining()))
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise SmokeFailure(
                f"{proc.log_path.name}: exited with code {proc.returncode} "
                f"before it was ready\n{log_tail(proc)}"
            )
        try:
            payload, _ = http_json(url, timeout=5.0)
            if ready(payload):
                return payload
        except (OSError, ValueError, SmokeFailure):
            pass    # not listening yet, or 503 while it comes up
        time.sleep(1.0)
    abort_hung(proc)
    raise SmokeFailure(
        f"{proc.log_path.name}: not ready within its time limit\n"
        f"{log_tail(proc, 80)}"
    )


# --------------------------------------------------------------- checks


def check_device(dev: dict, count: int, where: str) -> dict:
    """Normalize a child's device report and hold it to the run's."""
    got = {"platform": dev.get("platform"),
           "kind": dev.get("device_kind"), "count": dev.get("count")}
    if got["platform"] != PLATFORM:
        raise SmokeFailure(
            f"{where}: JAX platform is {got['platform']!r}, not "
            f"{PLATFORM!r} — no accelerator"
        )
    if got["count"] != count:
        raise SmokeFailure(
            f"{where}: {got['count']} device(s) visible, expected {count}"
        )
    return got


def check_losses(losses, where: str) -> None:
    if not losses or not all(math.isfinite(x) for x in losses):
        raise SmokeFailure(f"{where}: losses not finite: {losses}")
    # the same batch every step: decreasing, or flat to bf16 noise
    if losses[-1] > losses[0] + 0.05:
        raise SmokeFailure(f"{where}: loss went up: {losses}")


def prompts(vocab: int):
    gen = random.Random(SEED)
    return [[gen.randrange(1, vocab) for _ in range(n)] for n in PROMPT_LENS]


def generate(url: str, prompt, vocab: int):
    out, headers = http_json(
        f"{url}/generate",
        {"prompt": prompt, "max_new_tokens": NEW_TOKENS},
        timeout=max(5.0, min(180.0, remaining())),
    )
    ids = out.get("ids")
    if (not isinstance(ids, list) or len(ids) != NEW_TOKENS
            or not all(isinstance(t, int) and 0 <= t < vocab for t in ids)):
        raise SmokeFailure(f"{url}/generate returned bad ids: {out}")
    return ids, headers


# --------------------------------------------------------------- phases


def phase_native() -> None:
    """The C++ data/scheduler core: built from the committed sources
    (hash-keyed .so), or deliberately off — never silently absent."""
    t0 = time.monotonic()
    from mlcomp_tpu import native

    st = native.status()
    if not st["loaded"] and not st["disabled"]:
        raise SmokeFailure(
            f"native core build/load failed: {st['build_error']}"
        )
    emit({"phase": "native", "ok": True, "loaded": st["loaded"],
          "seconds": round(time.monotonic() - t0, 2)})


def phase_device(count: int, env=None) -> dict:
    """Ask a throwaway child what JAX sees, before anything expensive:
    on a host without the accelerator the run ends here."""
    t0 = time.monotonic()
    out = run_to_end(
        ["-c", "import json; from mlcomp_tpu.utils.chips import "
               "device_summary; print(json.dumps(device_summary()))"],
        "device.log", timeout=180.0, env=env,
    )
    dev = check_device(last_json_line(out, "platform"), count, "device probe")
    emit({"phase": "device", "ok": True, **dev,
          "seconds": round(time.monotonic() - t0, 2)})
    return dev


def phase_train(config: Path, name: str, count: int) -> dict:
    """``cli dag <config>``: the train stage's steps, losses and
    checkpoint, read back from the run's store."""
    t0 = time.monotonic()
    db = RUN / f"{name}.sqlite"
    run_to_end(
        ["-m", "mlcomp_tpu.cli", "dag", str(config), "--db", str(db),
         "--workdir", str(RUN / "work")],
        f"{name}.log", timeout=700.0,
    )
    from mlcomp_tpu.db.store import Store
    from mlcomp_tpu.io.storage import ModelStorage

    store = Store(str(db))
    try:
        dag_id = store.list_dags()[-1]["id"]
        task = next(r for r in store.task_rows(dag_id) if r["name"] == "train")
        if task["status"] != "success":
            raise SmokeFailure(f"{name}: task status {task['status']}")
        logs = [row["message"] for row in store.task_logs(task["id"])]
        losses = [v for _, v in store.metric_series(task["id"], "train/loss")]
        times = [v for _, v in store.metric_series(task["id"], "epoch_time_s")]
    finally:
        store.close()
    start = next((m for m in logs if "platform=" in m), None)
    if start is None:
        raise SmokeFailure(f"{name}: no device line in the task log: {logs}")
    m = re.search(
        r"platform=(\S+) device_kind='([^']*)' devices=(\d+)", start
    )
    if m is None:
        raise SmokeFailure(f"{name}: unreadable device line: {start}")
    dev = check_device(
        {"platform": m[1], "device_kind": m[2], "count": int(m[3])},
        count, f"{name} start line",
    )
    check_losses(losses, name)
    dag_name = config.stem
    ckpt = ModelStorage(str(RUN / "storage")).checkpoint_dir(
        "lm", dag_name, "train"
    )
    steps = sorted(int(p.name) for p in ckpt.iterdir() if p.name.isdigit())
    if not steps or steps[-1] != len(losses):
        raise SmokeFailure(
            f"{name}: no checkpoint at step {len(losses)} under {ckpt}"
        )
    steady = sorted(times[1:])[len(times[1:]) // 2] if len(times) > 1 else 0.0
    emit({
        "phase": name, "ok": True, "steps": len(losses), "losses": losses,
        "checkpoint_step": steps[-1], "start_line": start,
        # on a tpu backend the attention dispatch takes the flash
        # kernels at this length or raises (ops/attention.py)
        "attention": f"flash kernels, fwd+bwd ({dev['platform']})",
        # first step = compile + one step; later steps = one step
        "compile_seconds": round(times[0] - steady, 2),
        "step_seconds": [round(t, 3) for t in times],
        "seconds": round(time.monotonic() - t0, 2),
    })
    return {"losses": losses, "device": dev}


def start_daemon(name: str, extra, port: int, count: int):
    proc = spawn(
        ["-m", "mlcomp_tpu.cli", "serve", "--model", str(CONFIG),
         "--storage-task", "lm/lm_1p2b/train", "--port", str(port),
         *SERVE_ARGS, *extra],
        f"{name}.log",
    )
    url = f"http://127.0.0.1:{port}"
    health = wait_ready(proc, f"{url}/healthz", 600.0,
                        lambda h: h.get("ready"))
    dev = check_device(health.get("device") or {}, count, f"{name} /healthz")
    return proc, url, dev


def profile_kernels(url: str, vocab: int) -> dict:
    """Arm ``GET /profile`` and feed the daemon requests until the
    window closes; returns device ms under each required kernel."""
    result: dict = {}

    def arm():
        try:
            result["att"], _ = http_json(
                f"{url}/profile?dispatches=12",
                timeout=max(5.0, min(240.0, remaining())),
            )
        except Exception as e:  # surfaced below, in the main thread
            result["error"] = f"{type(e).__name__}: {e}"

    th = threading.Thread(target=arm, daemon=True)
    th.start()
    time.sleep(0.5)          # the capture arms before the traffic starts
    gen = random.Random(SEED + 1)
    # bucket-filling prompts: only a prompt whose FIRST chunk holds
    # tokens prefills that chunk through the flash kernel (left-padded
    # shorter prompts start mid-buffer, on the chunk kernel)
    for _ in range(16):      # every admission in the window prefills
        if not th.is_alive():
            break
        generate(
            url, [gen.randrange(1, vocab) for _ in range(max(PROMPT_LENS))],
            vocab,
        )
    th.join(timeout=max(1.0, min(240.0, remaining())))
    if th.is_alive() or "att" not in result:
        raise SmokeFailure(
            f"GET /profile did not resolve: {result.get('error')}"
        )
    att = result["att"]
    by_kernel = {
        want: round(sum(
            k["total_ms"] for k in att.get("kernels", [])
            if k["name"].startswith(want)
        ), 4)
        for want in KERNELS
    }
    missing = [k for k, ms in by_kernel.items() if ms <= 0]
    if missing or att.get("device_time_ms", 0) <= 0:
        raise SmokeFailure(
            f"GET /profile shows no device time under {missing}: "
            f"{json.dumps(att)[:2000]}"
        )
    return {"device_time_ms": att["device_time_ms"],
            "device_lanes": att.get("device_lanes"),
            "kernel_ms": by_kernel}


def phase_serve(name: str, extra, vocab: int, profile: bool) -> list:
    """One ``cli serve`` daemon: start, requests over HTTP, stop."""
    t0 = time.monotonic()
    proc, url, _ = start_daemon(name, extra, PORT, 1)
    try:
        ready_s = time.monotonic() - t0
        warm = last_json_line(
            Path(proc.log_path).read_text(errors="replace"), "warmup"
        )
        ids = [generate(url, p, vocab)[0] for p in prompts(vocab)]
        again, _ = generate(url, prompts(vocab)[0], vocab)
        if again != ids[0]:
            raise SmokeFailure(
                f"{name}: a repeated greedy prompt gave different ids: "
                f"{ids[0]} vs {again}"
            )
        line = {
            "phase": name, "ok": True, "requests": len(ids) + 1,
            "tokens_returned": (len(ids) + 1) * NEW_TOKENS,
            "repeat_identical": True,
            "ready_seconds": round(ready_s, 2),
            "compile_seconds": warm.get("seconds"),
            "programs": warm.get("programs"),
        }
        if profile:
            line["profile"] = profile_kernels(url, vocab)
        health, _ = http_json(f"{url}/healthz", timeout=10.0)
        eng = health.get("engine") or {}
        line.update({
            "matmul": f"quantize={health.get('quantize')}",
            "kv_layout": eng.get("kv_layout"),
            "peak_hbm_bytes": (health.get("device") or {}).get(
                "peak_bytes_in_use"),
            "seconds": round(time.monotonic() - t0, 2),
        })
        emit(line)
        return ids
    finally:
        kill(proc)


def phase_mesh_check(mesh_losses) -> None:
    """The one-device reference of the SPMD train stage, plus the
    layout of the sharded state (tools/mesh_train_check.py)."""
    t0 = time.monotonic()
    out = run_to_end(
        ["-m", "tools.mesh_train_check", str(MESH_CONFIG)],
        "mesh_check.log", timeout=600.0,
    )
    rep = last_json_line(out, "one_device_losses")
    one = rep["one_device_losses"]
    check_losses(one, "one-device reference")
    worst = max(abs(a - b) for a, b in zip(mesh_losses, one))
    problems = []
    if len(one) != len(mesh_losses) or worst > 0.05:
        problems.append(f"losses differ by {worst}: {mesh_losses} vs {one}")
    if rep["params_not_on_every_device"]:
        problems.append(
            f"not on every device: {rep['params_not_on_every_device'][:4]}"
        )
    if not rep["qkv_kernels"] or rep["qkv_kernels_without_tp"]:
        problems.append(
            f"q/k/v kernels without tp: {rep['qkv_kernels_without_tp'][:4]}"
        )
    if problems:
        raise SmokeFailure("mesh_check: " + "; ".join(problems))
    emit({
        "phase": "mesh_check", "ok": True, "mesh_losses": mesh_losses,
        "one_device_losses": one, "max_abs_loss_diff": round(worst, 5),
        "params": rep["params"], "params_on_every_device": True,
        "qkv_kernels": rep["qkv_kernels"], "qkv_spec": rep["qkv_spec"],
        "seconds": round(time.monotonic() - t0, 2),
    })


def phase_fleet(vocab: int) -> None:
    """``cli fleet --replicas 4 --chips 1``: four daemons, each pinned
    to its own chip, answering through the router."""
    t0 = time.monotonic()
    proc = spawn(
        ["-m", "mlcomp_tpu.cli", "fleet", "--model", str(CONFIG),
         "--storage-task", "lm/lm_1p2b_mesh/train",
         "--replicas", "4", "--chips", "1", "--port", str(PORT),
         "--port-range", f"{PORT + 1}:{PORT + 4}",
         "--registry", str(RUN / "fleet-registry.json"),
         "--log-dir", str(RUN / "fleet_logs"),
         # fleet adds --warmup itself; "=" keeps argparse from reading
         # the leading "--" of the value as an option
         "--serve-arg=" + " ".join(
             a for a in SERVE_ARGS if a != "--warmup")],
        "fleet.log",
    )
    url = f"http://127.0.0.1:{PORT}"
    try:
        try:
            status = wait_ready(proc, f"{url}/healthz", 700.0,
                                lambda s: s.get("live") == 4)
        except SmokeFailure as e:
            logs = sorted((RUN / "fleet_logs").glob("*.log"))
            tails = "\n".join(
                f"--- {p.name}\n" + "\n".join(
                    p.read_text(errors="replace").splitlines()[-12:])
                for p in logs
            )
            raise SmokeFailure(f"{e}\n{tails}")
        replicas = {}
        for r in status["replicas"]:
            health, _ = http_json(f"{r['url']}/healthz", timeout=10.0)
            check_device(health.get("device") or {}, 1, f"replica {r['name']}")
            replicas[r["name"]] = health["device"].get("visible_chips")
        if len(set(replicas.values())) != 4 or None in replicas.values():
            raise SmokeFailure(
                f"fleet: replicas do not hold four different chips: {replicas}"
            )
        gen = random.Random(SEED + 2)
        served = {}
        for _ in range(8):
            prompt = [gen.randrange(1, vocab) for _ in range(PROMPT_LENS[-1])]
            _, headers = generate(url, prompt, vocab)
            name = headers.get("x-mlcomp-replica") or headers.get(
                "X-Mlcomp-Replica")
            served[name] = served.get(name, 0) + 1
        emit({
            "phase": "fleet", "ok": True, "replicas": replicas,
            "requests": sum(served.values()), "served_by": served,
            "tokens_returned": sum(served.values()) * NEW_TOKENS,
            "seconds": round(time.monotonic() - t0, 2),
        })
    finally:
        kill(proc)


# ----------------------------------------------------------------- main


def model_vocab() -> int:
    import yaml

    with open(CONFIG) as f:
        return int(yaml.safe_load(f)["model"]["vocab_size"])


def run(chips: int) -> dict:
    shutil.rmtree(RUN, ignore_errors=True)
    RUN.mkdir(parents=True)
    sys.path.insert(0, str(ROOT))
    from mlcomp_tpu.utils.compile_cache import place_compile_cache

    cache = place_compile_cache()   # children inherit the variable
    emit({"phase": "start", "chips": chips, "compile_cache": cache,
          "cache_entries_at_start": (
              len(os.listdir(cache)) if os.path.isdir(cache) else 0)})
    phase_native()
    dev = phase_device(chips)
    vocab = model_vocab()
    if chips == 1:
        phase_train(CONFIG, "train", 1)
        dense = phase_serve("serve_dense", [], vocab, profile=True)
        paged = phase_serve("serve_paged", PAGED_ARGS, vocab, profile=False)
        if paged != dense:
            raise SmokeFailure(
                f"paged greedy ids differ from dense: {dense} vs {paged}"
            )
        emit({"phase": "paged_equals_dense", "ok": True,
              "prompts": len(dense)})
    else:
        mesh = phase_train(MESH_CONFIG, "train_mesh", chips)
        phase_mesh_check(mesh["losses"])
        phase_fleet(vocab)
    return dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1 (default): train -> serve dense -> serve paged on one "
        "chip.  4: only the path across chips (mesh train vs one "
        "device, four-replica fleet)",
    )
    args = ap.parse_args(argv)

    def on_signal(signum, _frame):
        raise SmokeFailure(f"signal {signum}")

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)
    try:
        dev = run(args.chips)
    except SmokeFailure as e:
        emit({"ok": False, "error": str(e)[-6000:]})
        return 1
    finally:
        kill_all()
    emit({"ok": True, "device": dev})
    return 0


if __name__ == "__main__":
    sys.exit(main())
