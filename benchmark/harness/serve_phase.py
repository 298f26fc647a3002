#!/usr/bin/env python3
"""One serving cell, once: the process that holds the chip.

Registers the configuration, starts the program's own server
(``ServingServer(batching="continuous", kv="paged")``), starts the load
generator as a separate process, follows its window announcements
(profiler on for the first seconds of the window in a traced run), and
writes what it saw to ``<out>/program.json`` for the parent: the
client's records, the engine's counters before and after, the request
span trees, the reduced trace, the compile count inside the window, the
device and its peak memory.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import common, program  # noqa: E402


def http_json(url: str, timeout: float = 30.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as response:
        return json.loads(response.read())


def engine_stats(engine, tries: int = 50) -> dict:
    """`/v1/stats` as the engine computes it. It reads the page pool's
    buffers while the engine thread donates them to the running step, so
    a read can land on a deleted array (PERF.md, what only the program
    can mend); the next try finds the step's output in place."""
    for attempt in range(tries):
        try:
            return engine.stats()
        except RuntimeError:
            if attempt == tries - 1:
                raise
            time.sleep(0.005)


def walk(spans: list):
    for span in spans:
        yield span
        yield from walk(span.get("children") or [])


def build_server(plan: dict, break_path: str | None = None):
    """The server, through the program's normal constructor."""
    from polyaxon_tpu.serving import ServingServer

    config = plan["config"]
    name, family, cfg = program.register(config, "serve")
    serve = config["serve"]
    server = ServingServer(
        name, seed=plan["seed"], batching="continuous", kv="paged",
        slots=serve["slots"], page_size=serve["page_size"],
        kv_pages=serve["kv_pages"], prefix_cache=True)
    if break_path == "token":
        # The test of the check itself: alter a token where it is
        # produced (every 7th decode step emits the runner-up's id + 1).
        engine = server.engine
        real = engine._step_plain
        count = [0]

        def broken(*args):
            nxt, cache = real(*args)
            count[0] += 1
            if count[0] % 7 == 0:
                nxt = (nxt + 1) % cfg.vocab_size
            return nxt, cache

        broken.kernels = {}
        engine._step_plain = broken
    return server, cfg


def run(plan: dict) -> dict:
    import jax

    device = common.device_info(plan["chips"], plan["require_chip"])
    compiles = common.CompileCounter()
    out_dir = plan["out_dir"]
    common.say({"phase": "serve", "note": "loading"}, device)
    server, cfg = build_server(plan, plan.get("break_path"))
    server.start()
    engine = server.engine
    samples: list[dict] = []
    edge_stats: dict = {}
    stop_sampling = threading.Event()
    trace_dir = os.path.join(out_dir, "trace")
    trace_state = {"on": False, "t_start": None, "t_stop": None}
    trace_lock = threading.Lock()

    def stop_trace():
        """Once, whoever comes first: the timer or the end of the run."""
        with trace_lock:
            if trace_state["on"]:
                trace_state["on"] = False
                jax.profiler.stop_trace()
                trace_state["t_stop"] = time.time()
    try:
        client_plan = {
            "host": server.host, "port": server.port,
            "traffic": plan["traffic"], "seed": plan["seed"],
            "slots": plan["config"]["serve"]["slots"],
            "vocab": cfg.vocab_size, "seconds": plan["seconds"],
            "temperature": plan["traffic"].get("temperature", 0.0),
            "eos_tokens": plan["traffic"].get("eos_tokens", []),
            "records": os.path.join(out_dir, "records.json")}
        plan_path = os.path.join(out_dir, "client_plan.json")
        common.write_json(plan_path, client_plan)
        client = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "client.py"), plan_path],
            stdout=subprocess.PIPE, env={
                k: v for k, v in os.environ.items()
                if not k.startswith(("JAX_", "XLA_", "TPU_"))},
            text=True)

        def sample_loop():
            # /v1/stats once a second, traced runs only: the pool's low
            # watermark is a per-layer metric.
            while not stop_sampling.wait(1.0):
                try:
                    s = engine_stats(engine)
                    samples.append({"t": time.time(),
                                    "kv_pages_free": s.get("kv_pages_free")})
                except Exception:  # noqa: BLE001 — a sample, not the run
                    pass

        sampler = None
        t_open = t_close = None
        failed_note = None
        trace_seconds = min(float(plan["seconds"]), float(plan["trace_seconds"]))
        try:
            for line in client.stdout:
                line = line.strip()
                if not line.startswith("{"):
                    continue
                event = json.loads(line)
                tag = event.get("event")
                if tag == "WINDOW_OPEN":
                    t_open = event["t"]
                    if plan["trace"]:
                        # The counters at the window's edges, the pool's
                        # samples and the span trees feed per-layer
                        # readers only: a timed run asks the engine
                        # nothing while it is being timed.
                        edge_stats["open"] = engine_stats(engine)
                        sampler = threading.Thread(target=sample_loop,
                                                   daemon=True)
                        sampler.start()
                        with jax.profiler.TraceAnnotation("bench:start_trace"):
                            jax.profiler.start_trace(
                            trace_dir, profiler_options=common.trace_options())
                        trace_state.update(on=True, t_start=time.time())

                        timer = threading.Timer(trace_seconds, stop_trace)
                        timer.daemon = True
                        timer.start()
                elif tag == "WINDOW_CLOSE":
                    t_close = event["t"]
                    if plan["trace"]:
                        edge_stats["close"] = engine_stats(engine)
                elif tag == "FAILED":
                    failed_note = event.get("why")
        finally:
            client.wait(timeout=120)
            stop_sampling.set()
            if sampler is not None:
                sampler.join(timeout=5)
            stop_trace()
        if failed_note or client.returncode != 0 or t_open is None:
            raise RuntimeError(f"load generator failed: {failed_note} "
                               f"(exit {client.returncode})")
        with open(client_plan["records"]) as fh:
            seen = json.load(fh)
        stats_after = engine_stats(engine)
        # Span trees of the requests due in the window (the ring keeps
        # the newest 256).
        timelines = {}
        for record in seen["records"]:
            if (not plan["trace"] or record["phase"] != "window"
                    or not record["request_id"]):
                continue
            try:
                tree = http_json(f"{server.url}/requests/"
                                 f"{record['request_id']}/timeline")
            except Exception:  # noqa: BLE001 — evicted from the ring
                continue
            timelines[record["request_id"]] = {
                span["name"]: span.get("duration_ms")
                for span in walk(tree.get("spans") or [])}
        peak = common.memory_peak(plan["chips"])
    finally:
        server.stop()
    result = {
        "kind": "serve", "device": {**device, "memory_peak_bytes": peak},
        "t_open": t_open, "t_close": t_close,
        "records": seen["records"],
        "stats": {**edge_stats, "after": stats_after},
        "samples": samples, "timelines": timelines,
        "compiles_in_window": compiles.between(t_open, t_close),
        "compiles_total": len(compiles.events),
        "trace_dir": trace_dir if plan["trace"] else None,
        "trace_span": [trace_state["t_start"], trace_state["t_stop"]],
        "model": {"vocab": cfg.vocab_size, "layers": cfg.n_layers},
    }
    return result


def main() -> None:
    from harness import phase_end

    with open(sys.argv[1]) as fh:
        plan = json.load(fh)
    try:
        result = run(plan)
    except common.NoChip as exc:
        common.fail(str(exc), code=3)
    phase_end.finish(plan, result)


if __name__ == "__main__":
    main()
