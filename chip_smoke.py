#!/usr/bin/env python3
"""The quickest proof that polyaxon_tpu still starts on the chip.

Runs the two hot paths once, end to end, through the entry points a user
would call, at the published widths of llama3_1b, with random weights
made from ``--seed``:

- *device*: refuses to go on unless JAX sees a TPU;
- *train*: ``plx run -f examples/chip_smoke_train.yaml --watch`` (control
  plane -> agent -> executor -> runtime loop), Pallas flash attention,
  depth cut to what one chip's memory holds, then the same job with
  ``attention_impl: xla`` as the reference for the first loss;
- *serve*: ``plx serve --batching continuous --kv paged`` at full depth,
  eight ``POST /v1/generate`` over HTTP, then the paged decode step
  through the Pallas kernel against the gather formulation on one pool.

``--multichip`` (four chips; the driver never passes it) runs instead
only what exists across chips: the train job on ``fsdp=4`` against a
one-device run of the same seed and batch, and ``plx serve --mesh tp=4``
against the one-chip model.

Process model: this parent never imports jax. A chip belongs to one
process at a time, so every phase runs in a child that is the only one
holding the chip while it runs, and the parent reads what the child
wrote. Children this script runs of itself are the ``--phase`` entries
at the bottom.

Each phase prints one JSON object (smoke output, labelled with the
device; nobody's benchmark). Any failed check exits non-zero. The last
line printed on success is the device line and nothing else:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")
TRAIN_FILE = os.path.join(HERE, "examples", "chip_smoke_train.yaml")

MODEL = "llama3_1b"
VOCAB = 128_256
DIM = 2048
FULL_DEPTH = 16
SEQ = 2048
# What a freshly initialised model's loss should be: logits that know
# nothing of the target cost ln(V) plus half their variance, and the
# head is a normal of std 0.02 cut at two sigmas (std x 0.88) applied to
# unit-RMS hidden states (models/common.py truncated_normal_init).
FIRST_LOSS = math.log(VOCAB) + 0.5 * DIM * (0.02 * 0.88) ** 2  # 12.08
# Depth and batch were fixed from the compiler's memory_analysis() for a
# described v5e chip before any chip time was spent (PERF.md, "Cells"):
# adamw at these widths is 8.6 GiB of state at 4 layers (the two vocab
# tables alone are 6.3 GB with their moments), and the flash step's
# temporaries take it to 12.6 GiB of 15.75 at batch 2. Depth 5 leaves
# 1.7 GiB, 6 leaves none; the xla-attention reference needs batch 1.
TRAIN = {"n_layers": 4, "global_batch_size": 2, "steps": 8}
REFERENCE = {"n_layers": 4, "global_batch_size": 1, "steps": 2}
# Four chips: global batch x4; the one-device side of the comparison
# holds the whole batch, which fits at depth 2 (13.0 GiB).
MULTI_TRAIN = {"n_layers": 2, "global_batch_size": 8, "steps": 6}

BF16_EPS = 2.0 ** -8
CHILDREN: list[subprocess.Popen] = []


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def check_placement(what: str, per_device: dict, total: int,
                    n_devices: int) -> None:
    """Every device holds part of the tree, and with several devices no
    one of them holds most of it: code that has only seen one chip can
    leave everything replicated, or whole on the first."""
    held = list(per_device.values())
    check(len(held) == n_devices and all(v > 0 for v in held),
          f"{what} is not on every device: {per_device}")
    check(n_devices == 1 or max(held) < 0.5 * total,
          f"{what} ({total} bytes) is not sharded: {per_device}")


def emit(phase: str, device: dict, **fields) -> None:
    print(json.dumps({"phase": phase, "device": device, **fields}),
          flush=True)


# ------------------------------------------------------------ child running
def spawn(cmd: list[str], log_name: str, env: dict | None = None
          ) -> tuple[subprocess.Popen, str]:
    os.makedirs(os.path.join(OUT, "logs"), exist_ok=True)
    log_path = os.path.join(OUT, "logs", log_name)
    child_env = {**os.environ, "POLYAXON_TPU_HOME": os.path.join(OUT, "plane"),
                 "PYTHONUNBUFFERED": "1", **(env or {})}
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(cmd, cwd=HERE, env=child_env, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
    CHILDREN.append(proc)
    return proc, log_path


def stop(proc: subprocess.Popen, grace: float = 30.0) -> None:
    """SIGINT first (the server's own teardown path, and a PJRT client
    releases the chip), then the whole process group, hard."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGINT)
            proc.wait(timeout=grace)
        except (ProcessLookupError, subprocess.TimeoutExpired):
            pass
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait(timeout=10)


def run(cmd: list[str], log_name: str, timeout: float,
        env: dict | None = None) -> str:
    """Run a child to its end; its combined output, or SmokeFailure."""
    proc, log_path = spawn(cmd, log_name, env)
    try:
        proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        stop(proc, grace=10)
        raise SmokeFailure(f"{log_name}: no end after {timeout:.0f}s")
    with open(log_path, errors="replace") as fh:
        text = fh.read()
    if proc.returncode != 0:
        raise SmokeFailure(f"{log_name}: exit code {proc.returncode}\n"
                           + text[-3000:])
    return text


def run_self(phase: str, timeout: float, *args: str) -> dict:
    """One of this script's own ``--phase`` children; its last JSON line."""
    text = run([sys.executable, os.path.abspath(__file__), "--phase", phase,
                *args], f"{phase}.log", timeout)
    for line in reversed(text.strip().splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise SmokeFailure(f"{phase}: no JSON result\n{text[-2000:]}")


# ------------------------------------------------------------------- train
def cli_train(tag: str, params: dict, seed: int, timeout: float = 600) -> dict:
    """One JAXJob through the CLI; its outputs and per-step losses."""
    cmd = [sys.executable, "-m", "polyaxon_tpu.cli", "run", "-f", TRAIN_FILE,
           "--name", tag, "--watch"]
    for name, value in {**params, "seed": seed}.items():
        cmd += ["-P", f"{name}={value}"]
    t0 = time.time()
    text = run(cmd, f"train-{tag}.log", timeout)
    wall = time.time() - t0
    found = re.search(r"Run created: (\w+)", text)
    check(found is not None, f"train-{tag}: the CLI named no run")
    run_dir = os.path.join(OUT, "plane", "artifacts", found.group(1))
    with open(os.path.join(run_dir, "outputs.json")) as fh:
        outputs = json.load(fh)
    events = {}
    for metric in ("loss", "step_time_ms"):
        with open(os.path.join(run_dir, "events", "metric",
                               f"{metric}.jsonl")) as fh:
            events[metric] = [json.loads(line)["value"] for line in fh]
    return {"wall_s": round(wall, 1), "outputs": outputs,
            "losses": events["loss"], "step_time_ms": events["step_time_ms"],
            **params}


def check_training(result: dict, n_devices: int) -> None:
    losses, outputs = result["losses"], result["outputs"]
    check(len(losses) == result["steps"],
          f"{len(losses)} losses for {result['steps']} steps")
    check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    check(abs(losses[0] - FIRST_LOSS) < 0.3,
          f"first loss {losses[0]:.3f} is not the fresh model's "
          f"{FIRST_LOSS:.3f} +- 0.3 (ln {VOCAB} = {math.log(VOCAB):.3f} "
          "plus half the logit variance at init)")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(outputs["steps"] == result["steps"], "run stopped early")
    # The loop compiles its step once, ahead of time, and runs that
    # executable for every step (runtime/loop.py): the kernels named
    # here are the ones every reported loss went through.
    kernels = outputs["step_kernels"]
    check(all(kernels.get(name, 0) >= 1 for name in
              ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq")),
          f"compiled step lacks the flash kernels: {kernels}")
    check_placement("params", outputs["param_bytes_per_device"],
                    4 * outputs["param_count"], n_devices)  # f32 masters


def train_line(result: dict) -> dict:
    outputs = result["outputs"]
    return {
        "model": MODEL, "seq": SEQ, "n_layers": result["n_layers"],
        "depth_cut": f"{result['n_layers']} of {FULL_DEPTH} layers "
                     "(optimizer state; see PERF.md)",
        "global_batch_size": result["global_batch_size"],
        "wall_s": result["wall_s"],
        "compile_s": round(outputs["compile_time_s"], 1),
        "compile_cache": outputs["compile_cache"],
        "step_time_ms": [round(x, 1) for x in result["step_time_ms"]],
        "losses": [round(x, 4) for x in result["losses"]],
        "kernels": outputs["step_kernels"],
        "param_bytes_per_device": outputs["param_bytes_per_device"],
        "peak_hbm_bytes": outputs["peak_hbm_bytes"],
    }


def train_phase(device: dict, seed: int) -> None:
    flash = cli_train("flash", {**TRAIN, "attention_impl": "flash"}, seed)
    check_training(flash, n_devices=1)
    emit("train", device, **train_line(flash))
    # The reference for the first loss: same seed, same data, einsum
    # attention. It needs batch 1 to fit, so the kernel runs there too.
    pair = {impl: cli_train(f"ref-{impl}",
                            {**REFERENCE, "attention_impl": impl}, seed)
            for impl in ("flash", "xla")}
    first = {impl: r["losses"][0] for impl, r in pair.items()}
    check(not pair["xla"]["outputs"]["step_kernels"],
          "the xla reference compiled a Mosaic kernel")
    # One bf16 rounding of a ~12 loss is 0.05; the two programs differ
    # by attention alone, averaged over 2048 positions.
    check(abs(first["flash"] - first["xla"]) < 0.02,
          f"first loss differs, flash vs xla: {first}")
    emit("train_reference", device, first_loss=first,
         global_batch_size=REFERENCE["global_batch_size"],
         n_layers=REFERENCE["n_layers"],
         peak_hbm_bytes={impl: r["outputs"]["peak_hbm_bytes"]
                         for impl, r in pair.items()})


# ------------------------------------------------------------------- serve
def http(url: str, payload: dict | None = None, timeout: float = 600) -> dict:
    data = None if payload is None else json.dumps(payload).encode()
    request = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(request, timeout=timeout) as response:
        return json.loads(response.read())


def free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def smoke_requests(seed: int) -> dict[str, dict]:
    """Prompts of 100-1,000 tokens from the seed. `shared_a/b` open with
    the same 256 tokens; `again_1/2` repeat `short` exactly."""
    rng = random.Random(seed)

    def tokens(n: int) -> list[int]:
        return [rng.randrange(VOCAB) for _ in range(n)]

    prefix, short = tokens(256), tokens(100)
    return {
        "short": {"tokens": short, "new": 32},
        "long": {"tokens": tokens(1000), "new": 48},
        "shared_a": {"tokens": prefix + tokens(200), "new": 64},
        "mid": {"tokens": tokens(640), "new": 32},
        "odd": {"tokens": tokens(333), "new": 40},
        "shared_b": {"tokens": prefix + tokens(300), "new": 64},
        "again_1": {"tokens": short, "new": 32},
        "again_2": {"tokens": short, "new": 32},
    }


def generate_wave(url: str, wave: dict[str, dict]) -> dict[str, dict]:
    """Every request of the wave in flight at once."""
    results: dict[str, dict] = {}

    def one(name: str, spec: dict) -> None:
        t0 = time.time()
        try:
            reply = http(f"{url}/v1/generate",
                         {"tokens": [spec["tokens"]],
                          "max_new_tokens": spec["new"], "temperature": 0.0})
            results[name] = {"tokens": reply["tokens"][0],
                             "seconds": round(time.time() - t0, 2)}
        except (OSError, ValueError, KeyError) as exc:
            results[name] = {"error": f"{type(exc).__name__}: {exc}"}

    threads = [threading.Thread(target=one, args=item) for item in wave.items()]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join(timeout=900)
    return results


def serve_and_query(tag: str, seed: int, extra_args: list[str],
                    n_devices: int) -> dict:
    port = free_port()
    url = f"http://127.0.0.1:{port}"
    t0 = time.time()
    server, log_path = spawn(
        [sys.executable, "-m", "polyaxon_tpu.cli", "serve", "--model", MODEL,
         "--batching", "continuous", "--kv", "paged", "--seed", str(seed),
         "--port", str(port), *extra_args], f"{tag}.log")
    try:
        health = None
        while health is None:
            check(server.poll() is None and time.time() - t0 < 600,
                  f"{tag}: server not healthy\n"
                  + open(log_path, errors="replace").read()[-3000:])
            try:
                health = http(f"{url}/healthz", timeout=5)
            except (OSError, ValueError):
                time.sleep(1.0)
        ready_s = time.time() - t0
        check(health.get("status") == "ok" and health.get("model") == MODEL,
              f"{tag}: /healthz says {health}")

        requests = smoke_requests(seed)
        names = list(requests)
        results = {}
        # Five in flight over four slots; then the prefix sharer and the
        # first repeat with their pages resident; then the second repeat.
        for wave in (names[:5], names[5:7], names[7:]):
            results.update(generate_wave(
                url, {name: requests[name] for name in wave}))
        stats = http(f"{url}/v1/stats")
        check(http(f"{url}/healthz", timeout=30).get("status") == "ok",
              f"{tag}: /healthz after the requests")
    finally:
        stop(server)

    for name, spec in requests.items():
        got = results.get(name, {})
        check("error" not in got, f"{tag}: request {name}: {got.get('error')}")
        check(len(got["tokens"]) == spec["new"],
              f"{tag}: {name} returned {len(got['tokens'])} tokens, "
              f"asked {spec['new']}")
        check(all(isinstance(t, int) and 0 <= t < VOCAB
                  for t in got["tokens"]), f"{tag}: {name} ids outside vocab")
    # The two repeats run the same programs on the same resident pages;
    # the first request of the three took the whole-prompt prefill, whose
    # numerics differ, so against it agreement is reported, not required
    # (a random model's argmax flips on noise).
    check(results["again_1"]["tokens"] == results["again_2"]["tokens"],
          f"{tag}: a repeated greedy request changed its answer")
    check(stats["requests_served"] == len(requests),
          f"{tag}: served {stats['requests_served']} of {len(requests)}")
    check(stats["prefill_tokens_skipped"] >= 256 + 2 * 96,
          f"{tag}: the radix cache skipped only "
          f"{stats['prefill_tokens_skipped']} prompt tokens")
    check(stats["kv_invariant_violations"] == 0 and not stats["step_failures"],
          f"{tag}: engine faults in {stats}")
    check(stats["decode_kernels"].get("paged_decode", 0) >= 1,
          f"{tag}: compiled decode step lacks the paged kernel: "
          f"{stats['decode_kernels']}")
    held = stats["device"]
    for tree in ("param", "kv"):
        check_placement(f"{tag}: {tree}", held[f"{tree}_bytes_per_device"],
                        held[f"{tree}_bytes"], n_devices)
    return {
        "model": MODEL, "n_layers": FULL_DEPTH, "ready_s": round(ready_s, 1),
        "wall_s": round(time.time() - t0, 1),
        "request_seconds": {n: results[n]["seconds"] for n in names},
        "repeat_matches_first":
            results["short"]["tokens"] == results["again_1"]["tokens"],
        "compile_cache": stats["compile_cache"],
        "decode_steps": stats["decode_steps"],
        "prefill_tokens_skipped": stats["prefill_tokens_skipped"],
        "kernels": stats["decode_kernels"],
        "param_bytes_per_device": held["param_bytes_per_device"],
        "kv_bytes_per_device": held["kv_bytes_per_device"],
        "peak_hbm_bytes": held["peak_hbm_bytes"],
    }


def check_parity(result: dict, what: str) -> None:
    check(result["max_abs_err"] <= result["bound"],
          f"{what}: max abs logit error {result['max_abs_err']:.4f} over "
          f"the bf16 bound {result['bound']:.4f}")


def serve_phase(device: dict, seed: int) -> None:
    emit("serve", device, **serve_and_query("serve", seed, [], n_devices=1))
    parity = run_self("paged-parity", 600, "--seed", str(seed))
    check_parity(parity, "pallas vs gather")
    check(parity["kernels"] == {"pallas": {"paged_decode": 1}, "gather": {}},
          f"parity programs hold {parity['kernels']}")
    emit("paged_parity", device, **parity)


# --------------------------------------------------------------- multichip
def multichip_phases(device: dict, seed: int) -> None:
    n = device["count"]
    sharded = cli_train("fsdp4", {**MULTI_TRAIN, "attention_impl": "flash"},
                        seed)
    check_training(sharded, n_devices=n)
    emit("train_fsdp", device, **train_line(sharded))
    single = run_self("ref-train", 600, "--seed", str(seed))
    # Same data, same init; the reductions run in another order.
    check(abs(single["losses"][0] - sharded["losses"][0]) < 0.02,
          f"first loss, one device {single['losses'][0]} vs fsdp "
          f"{sharded['losses'][0]}")
    check(single["losses"][-1] < single["losses"][0],
          f"one-device loss did not fall: {single['losses']}")
    emit("train_one_device", device, **single)

    emit("serve_tp", device,
         **serve_and_query("serve-tp", seed, ["--mesh", f"tp={n}"],
                           n_devices=n))
    parity = run_self("tp-parity", 900, "--seed", str(seed))
    check_parity(parity, f"tp={n} vs one chip")
    emit("tp_parity", device, **parity)


# ------------------------------------------- children of this script (jax)
def child_device() -> dict:
    import importlib.metadata

    import jax
    import jaxlib

    devices = jax.devices()
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": importlib.metadata.version("libtpu"),
            "python": sys.version.split()[0],
            "platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def _decode_fixture(seed: int, max_len: int = 2048, page: int = 16):
    """Prompts, block tables and decode inputs for four rows (one idle)
    of ragged length, with page ids deliberately out of order."""
    import numpy as np

    rng = np.random.default_rng(seed)
    lengths = [700, 300, 40]
    maxp = max_len // page
    tables = np.full((4, maxp), -1, np.int32)
    free = list(rng.permutation(np.arange(1, 200)))
    for row, length in enumerate(lengths):
        for slot in range(length // page + 1):
            tables[row, slot] = free.pop()
    prompts = [rng.integers(0, VOCAB, (1, n), dtype=np.int32)
               for n in lengths]
    tokens = rng.integers(0, VOCAB, (4,), dtype=np.int32)
    pos = np.asarray(lengths + [-1], np.int32)
    return prompts, tables, tokens, pos


def _paged_logits(cfg, params, cache, fixture, page: int = 16):
    """Prefill the fixture's rows into `cache`, then one paged decode
    step; (logits of the live rows, the compiled step's kernels)."""
    import jax
    import numpy as np

    from polyaxon_tpu.models import llama
    from polyaxon_tpu.perf.hlo import pallas_kernels

    prompts, tables, tokens, pos = fixture

    @jax.jit
    def prefill(params, prompt, cache, page_ids):
        k_all, v_all = llama.paged_prefill_kv(cfg, params, prompt)
        return llama.paged_insert_prefill(cache, k_all, v_all, page_ids, page)

    for row, prompt in enumerate(prompts):
        cache = prefill(params, prompt, cache, tables[row])
    step = jax.jit(lambda *a: llama.decode_step_paged(cfg, *a)[0]).lower(
        params, cache, tokens, pos, tables).compile()
    logits = np.asarray(step(params, cache, tokens, pos, tables))
    check(bool(np.isfinite(logits).all()), "decode logits not finite")
    return logits[:len(prompts)], cache, pallas_kernels(step.as_text())


def _bound(logits) -> float:
    """One bf16 rounding of the residual stream per layer of depth,
    against the logits' own scale: set from the dtype, not the result."""
    import numpy as np

    return float(FULL_DEPTH * BF16_EPS * max(1.0, np.abs(logits).max()))


def child_paged_parity(seed: int) -> dict:
    """Paged decode at full width and depth: the Pallas kernel against
    the gather formulation, same weights, same pool."""
    import dataclasses

    import jax
    import numpy as np

    from polyaxon_tpu.models import llama
    from polyaxon_tpu.serving.server import load_params

    cfg, params = load_params(MODEL, seed=seed)
    fixture = _decode_fixture(seed)
    pool = llama.paged_init_cache(cfg, 256, 16)
    by_impl, kernels = {}, {}
    for impl in ("pallas", "gather"):
        by_impl[impl], pool, kernels[impl] = _paged_logits(
            dataclasses.replace(cfg, paged_attention_impl=impl), params,
            pool, fixture)
    err = float(np.abs(by_impl["pallas"] - by_impl["gather"]).max())
    return {"max_abs_err": err, "bound": _bound(by_impl["gather"]),
            "logits_absmax": float(np.abs(by_impl["gather"]).max()),
            "kernels": kernels,
            "peak_hbm_bytes": (jax.devices()[0].memory_stats() or {}).get(
                "peak_bytes_in_use")}


def child_tp_parity(seed: int) -> dict:
    """The server's own tp-sharded params and pool against a one-chip
    copy of the same model: logits of the same paged decode step."""
    import jax
    import numpy as np

    from polyaxon_tpu.models import llama
    from polyaxon_tpu.parallel.sharding import bytes_per_device, param_bytes
    from polyaxon_tpu.serving import ServingServer
    from polyaxon_tpu.serving.server import load_params

    n = len(jax.devices())
    server = ServingServer(MODEL, seed=seed, batching="continuous",
                           kv="paged", mesh_axes={"tp": n})
    engine = server.engine
    try:
        fixture = _decode_fixture(seed)
        with server.mesh:
            sharded, _, kernels = _paged_logits(
                engine.cfg, engine.params, engine._cache, fixture)
        held = {}
        for name, tree in (("params", engine.params), ("kv", engine._cache)):
            held[name] = bytes_per_device(tree)
            check_placement(name, held[name], param_bytes(tree), n)
    finally:
        server.httpd.server_close()
        engine.stop()
    cfg, params = load_params(MODEL, seed=seed)
    single, _, _ = _paged_logits(cfg, params,
                                 llama.paged_init_cache(cfg, 256, 16), fixture)
    return {"max_abs_err": float(np.abs(sharded - single).max()),
            "bound": _bound(single), "kernels": kernels,
            "bytes_per_device": held}


def child_ref_train(seed: int) -> dict:
    """The four-chip train job, compiled from the same Polyaxonfile, on
    jax.devices()[:1]."""
    import jax

    from polyaxon_tpu.compiler.compile import ENV_JAXJOB_SPEC, compile_operation
    from polyaxon_tpu.polyaxonfile import (check_polyaxonfile,
                                           resolve_operation_context)
    from polyaxon_tpu.polyflow import V1JAXJob
    from polyaxon_tpu.runtime import run_jaxjob

    params = {**MULTI_TRAIN, "attention_impl": "flash", "seed": seed}
    op = resolve_operation_context(
        check_polyaxonfile(TRAIN_FILE, params=params), params=params,
        run_uuid="one-device")
    plan = compile_operation(op, run_uuid="one-device",
                             artifacts_root=os.path.join(OUT, "ref-train"))
    job = V1JAXJob.from_dict(
        json.loads(plan.processes[0].env[ENV_JAXJOB_SPEC]))
    losses: list[float] = []
    result = run_jaxjob(job, devices=jax.devices()[:1], mesh_axes={"dp": 1},
                        on_metrics=lambda _, m: losses.append(m["loss"]))
    return {"losses": losses, "kernels": result.step_kernels,
            "peak_hbm_bytes": result.peak_hbm_bytes, **MULTI_TRAIN}


PHASES = {"device": lambda seed: child_device(),
          "paged-parity": child_paged_parity,
          "tp-parity": child_tp_parity,
          "ref-train": child_ref_train}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--multichip", action="store_true",
                        help="four chips: only the fsdp=4 train job and "
                             "the tp=4 server, each with what it is "
                             "compared with")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--phase", choices=sorted(PHASES),
                        help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.phase:
        print(json.dumps(PHASES[args.phase](args.seed)), flush=True)
        return 0

    try:
        found = run_self("device", 300)
        device = {k: found[k] for k in ("platform", "kind", "count")}
        check(device["platform"] == "tpu",
              f"no accelerator: jax reports platform `{device['platform']}`")
        check(device["count"] == (4 if args.multichip else 1),
              f"{device['count']} chips: run on one chip with no "
              "arguments, on four with --multichip")
        emit("device", device, **{k: found[k] for k in
                                  ("jax", "jaxlib", "libtpu", "python")})
        if args.multichip:
            multichip_phases(device, args.seed)
        else:
            train_phase(device, args.seed)
            serve_phase(device, args.seed)
    except SmokeFailure as exc:
        print(f"chip_smoke FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    finally:
        for proc in CHILDREN:
            stop(proc, grace=5)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
