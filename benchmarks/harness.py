"""What every kind of cell shares: resolving a cell from files, the compile
cache, the compile counter, the traced sub-window, the result line."""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def read_json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, bench_dir: str = HERE) -> dict:
    """A cell, resolved by name alone: BENCHMARK.json's entry →
    configs/<config>.json, traffic/<traffic>.json, kinds/<kind>.py. A later
    PR adds a cell by adding files and an entry; nothing here names one."""
    spec = read_json(os.path.dirname(bench_dir), "BENCHMARK.json")
    entries = [w for w in spec["workloads"] if w["name"] == workload]
    if len(entries) != 1:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         "BENCHMARK.json")
    return resolve(entries[0], spec, bench_dir)


def resolve(entry: dict, spec: dict, bench_dir: str = HERE) -> dict:
    """The files of one `workloads` entry (tools also resolve a cell that
    BENCHMARK.json does not list yet, with an empty `spec`)."""
    workload = entry["name"]
    config = read_json(bench_dir, "configs", entry["config"] + ".json")
    traffic = read_json(bench_dir, "traffic", entry["traffic"] + ".json")
    if int(traffic.get("chips", 1)) != int(entry["chips"]):
        raise SystemExit(f"benchmark: {workload}: BENCHMARK.json asks for "
                         f"{entry['chips']} chip(s), the traffic file for "
                         f"{traffic.get('chips', 1)}")
    kind_path = os.path.join(bench_dir, "kinds", traffic["kind"] + ".py")
    if not os.path.exists(kind_path):
        raise SystemExit(f"benchmark: no kind {traffic['kind']!r}")

    def reported(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]

    return {"name": workload, "entry": entry, "config": config,
            "traffic": traffic, "chips": int(entry["chips"]),
            "kind": load_module(kind_path, "kind_" + traffic["kind"]),
            "end_to_end": reported(spec.get("end_to_end", [])),
            "per_layer": reported(spec.get("per_layer", [])),
            "bench_dir": bench_dir}


def layer_reader(name: str, bench_dir: str = HERE):
    """The reader of a per-layer metric, found by the metric's name:
    layer_metrics/<name>.py, or layer_metrics/<family>.py for
    `<family>.<variant>` (the variant is handed to the reader)."""
    family, _, variant = name.partition(".")
    for stem, var in ((name, ""), (family, variant)):
        path = os.path.join(bench_dir, "layer_metrics", stem + ".py")
        if os.path.exists(path):
            mod = load_module(path, "layer_" + stem.replace(".", "_"))
            return lambda spans, trace, counters: mod.compute(
                spans, trace, dict(counters, variant=var))
    raise SystemExit(f"benchmark: per-layer metric {name!r} has no reader "
                     "under layer_metrics/")


def build_config(cell: dict, extra: dict, rehearse: dict | None = None):
    """The program's Config for this cell: the preset, then the
    configuration file's overrides, then the traffic file's, then the
    run's own (seed, directories). Every cell turns the program's
    continuous profiler off: it would trace inside the timed window."""
    from novel_view_synthesis_3d_tpu.config import get_preset

    over = {"obs.profile.every_steps": 0,
            "obs.profile.serve_every_dispatches": 0}
    over.update(cell["config"].get("overrides", {}))
    over.update(cell["traffic"].get("overrides", {}))
    over.update(extra)
    if rehearse:
        over.update(rehearse.get("overrides", {}))
    return get_preset(cell["config"]["preset"]).override(**over).validate()


def model_sizes(cfg) -> dict:
    """The sizes the reference and flops.py need, read off the program's
    config object (inputs, nothing computed)."""
    m = cfg.model
    return {"ch": m.ch, "ch_mult": list(m.ch_mult), "emb_ch": m.emb_ch,
            "num_res_blocks": m.num_res_blocks,
            "attn_resolutions": list(m.attn_resolutions),
            "attn_heads": m.attn_heads, "side": cfg.data.img_sidelength}


class CompileCounter:
    """Programs built while `armed`: backend compilations and
    persistent-cache loads alike (jax.monitoring). Any inside the measured
    window makes the run incorrect."""

    _EVENTS = ("/jax/core/compile/backend_compile_duration",
               "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.armed = False
        self.in_window = 0
        self.total = 0
        self.total_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event in self._EVENTS:
            self.total += 1
            self.total_s += secs
            if self.armed:
                self.in_window += 1


class TraceWindow:
    """The traced sub-window of a `--trace 1` run: the benchmark's own
    jax.profiler capture, opened at the window's start and closed after
    `seconds`. `poll()` is called at call boundaries (or by `run_timer`)."""

    def __init__(self, enabled: bool, out_dir: str, seconds: float):
        self.enabled = enabled
        self.out_dir = out_dir
        self.seconds = seconds
        self.t0 = self.t1 = None
        self._lock = threading.Lock()

    def start(self):
        if self.enabled and self.t0 is None:
            import jax

            os.makedirs(self.out_dir, exist_ok=True)
            jax.profiler.start_trace(self.out_dir)
            self.t0 = time.perf_counter()

    def poll(self, force: bool = False):
        with self._lock:
            if (self.enabled and self.t0 is not None and self.t1 is None
                    and (force or time.perf_counter() - self.t0
                         >= self.seconds)):
                import jax

                self.t1 = time.perf_counter()
                jax.profiler.stop_trace()

    def run_timer(self):
        """For kinds whose loop the benchmark does not sit in."""
        def waiter():
            time.sleep(self.seconds)
            self.poll(force=True)
        th = threading.Thread(target=waiter, name="bench-trace-window",
                              daemon=True)
        th.start()
        return th

    def xplane(self):
        import glob

        files = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return files[-1] if files else None


def memory_peaks(chips: int) -> dict:
    """The allocator's peaks on the fullest chip, read when the window
    has closed and before the reference runs, so that they are the
    program's. `reserved` is what the allocator took from the chip's
    memory and holds a program's temporaries; `in_use` omits them on this
    runtime (a 9.78 GB train step reads 4.27 GB there)."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    return {"reserved": max(int(s.get("peak_bytes_reserved", 0))
                            for s in stats),
            "in_use": max(int(s.get("peak_bytes_in_use", 0))
                          for s in stats)}


def device_info(chips: int, memory: dict) -> dict:
    import jax

    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(memory["reserved"], memory["in_use"])}


def compare(name: str, value: float, limit: float, numbers: list) -> bool:
    """One compared number beside its limit (printed in every run)."""
    ok = bool(value <= limit)  # NaN fails
    numbers.append({"name": name, "value": float(value),
                    "limit": float(limit), "ok": ok})
    print(f"compare {name}: {value:.6g} (limit {limit:.6g}) "
          f"{'ok' if ok else 'FAILED'}", flush=True)
    return ok


def log(msg: str):
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)
