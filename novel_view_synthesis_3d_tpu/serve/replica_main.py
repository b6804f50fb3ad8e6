"""Replica process entrypoint:

    python -m novel_view_synthesis_3d_tpu.serve.replica_main spec.json

One fleet replica = one OS process owning its own JAX runtime, mesh,
SamplingService, registry watcher, and telemetry directory. The spec
file (JSON) describes everything; the process answers the replica
handle protocol over HTTP (serve/replica.py ReplicaServer) and writes
`ready_file` ({"port", "pid", "url"}) once it is accepting traffic —
the fleet launcher (serve_bench --fleet, `nvs3d route`) polls for it
instead of racing the bind.

Once serving, a daemon thread touches `ready_file`'s mtime every
`heartbeat_s` (default 2.0) — the fleet supervisor's liveness signal: a
process that is alive but wedged (event loop stuck, not just slow)
stops heartbeating, and heartbeat age is checkable with a stat, no HTTP
round-trip to a possibly-hung server.

Spec keys:
    name            fleet identity (required)
    results_folder  this replica's telemetry dir (required; fleet trace
                    reconstruction reads <fleet_dir>/replica_<name>/)
    ready_file      path to write the readiness JSON (required)
    heartbeat_s     ready-file mtime touch period (default 2.0)
    preset          config preset (default "tiny64")
    sidelength      image sidelength override (default 16)
    steps           diffusion.sample_timesteps (default 4)
    overrides       {dotted.key: value} extra config overrides
    port            bind port (default 0 = ephemeral)
    jax_cache_dir   shared persistent compile cache, used when
                    JAX_COMPILATION_CACHE_DIR is unset (optional;
                    default <checkout>/.jax_cache, which the replicas of
                    one checkout share anyway: N pay one compile)
    env             chip overlay the launcher applies to this process's
                    environment (serve/fleet_supervisor.assign_chips)
    registry        {"dir": ..., "channel": ..., "poll_s": ...} —
                    subscribe a RegistryWatcher; initial weights load
                    from the channel head when it points at a version

Without a registry (or with an empty channel) the replica builds
SYNTHETIC weights: model.init with a fixed seed, so every replica in a
fleet holds byte-identical params — orbit failover continuations are
seamless across replicas by construction.

SIGTERM/SIGINT runs the PR 11 drain state machine (admissions reject
retryably, queued + in-ring work finishes) before exit — `kill -TERM`
IS the graceful retirement path; `kill -9` is what the chaos lane does.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading


def _build_synthetic(cfg):
    """Deterministic synthetic weights (mirrors tools/serve_bench.build:
    fixed-seed model.init on a synthetic batch)."""
    import jax
    import jax.numpy as jnp

    from novel_view_synthesis_3d_tpu.data.synthetic import (
        make_example_batch)
    from novel_view_synthesis_3d_tpu.models import build_denoiser

    model = build_denoiser(cfg.model)
    batch = make_example_batch(
        batch_size=8, sidelength=cfg.data.img_sidelength, seed=0)
    mb = {
        "x": jnp.asarray(batch["x"]), "z": jnp.asarray(batch["target"]),
        "logsnr": jnp.zeros((batch["x"].shape[0],)),
        "R1": jnp.asarray(batch["R1"]), "t1": jnp.asarray(batch["t1"]),
        "R2": jnp.asarray(batch["R2"]), "t2": jnp.asarray(batch["t2"]),
        "K": jnp.asarray(batch["K"]),
    }
    params = model.init(
        {"params": jax.random.PRNGKey(0),
         "dropout": jax.random.PRNGKey(1)},
        mb, cond_mask=jnp.ones((batch["x"].shape[0],)),
        train=False)["params"]
    return model, params


def _heartbeat(ready_file: str, stop: "threading.Event",
               period_s: float) -> None:
    """Touch the ready file's mtime every `period_s` while serving. The
    faultinject heartbeat-stop hook freezes it (wedged-process drill)."""
    from novel_view_synthesis_3d_tpu.utils import faultinject

    while not stop.wait(period_s):
        if faultinject.serve_heartbeat_stopped():
            continue
        try:
            os.utime(ready_file, None)
        except OSError:
            pass  # file mid-replace by a supervisor respawn: skip one


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python -m novel_view_synthesis_3d_tpu.serve."
              "replica_main <spec.json>", file=sys.stderr)
        return 2
    with open(argv[0]) as fh:
        spec = json.load(fh)

    from novel_view_synthesis_3d_tpu.utils.xla_cache import (
        setup_compilation_cache)

    setup_compilation_cache(default_dir=spec.get("jax_cache_dir"),
                            min_entry_bytes=0)

    from novel_view_synthesis_3d_tpu import obs
    from novel_view_synthesis_3d_tpu.config import get_preset
    from novel_view_synthesis_3d_tpu.sample.service import SamplingService
    from novel_view_synthesis_3d_tpu.serve.replica import (
        LocalReplica,
        ReplicaServer,
    )

    name = spec["name"]
    results_folder = spec["results_folder"]
    os.makedirs(results_folder, exist_ok=True)
    cfg = get_preset(spec.get("preset", "tiny64")).override(**{
        "data.img_sidelength": int(spec.get("sidelength", 16)),
        "diffusion.sample_timesteps": int(spec.get("steps", 4)),
        "serve.results_folder": results_folder,
    })
    if spec.get("overrides"):
        cfg = cfg.override(**dict(spec["overrides"]))
    cfg = cfg.validate()

    model, params = _build_synthetic(cfg)
    model_version = ""
    store = None
    reg_spec = spec.get("registry") or {}
    if reg_spec.get("dir"):
        from novel_view_synthesis_3d_tpu.registry import RegistryStore

        store = RegistryStore(reg_spec["dir"])
        vid = store.read_channel(reg_spec.get("channel", "stable"))
        if vid:
            params = store.load_params(vid)
            model_version = vid

    telemetry = obs.RunTelemetry.create(cfg.obs, results_folder)
    profiler = (obs.make_profiler(cfg.obs.profile, results_folder,
                                  cfg.model, telemetry.bus,
                                  telemetry.registry, unit="dispatch")
                if cfg.obs.enabled else None)
    service = SamplingService(
        model, params, cfg.diffusion, cfg.serve,
        results_folder=results_folder, tracer=telemetry.tracer,
        flight=telemetry.flight, profiler=profiler,
        model_version=model_version)
    watcher = None
    if store is not None:
        from novel_view_synthesis_3d_tpu.registry import RegistryWatcher

        bus = telemetry.bus
        watcher = RegistryWatcher(
            service, store, reg_spec.get("channel", "stable"),
            poll_s=float(reg_spec.get("poll_s", 2.0)),
            event_cb=lambda s, kind, detail, version="": bus.event(
                s, kind, detail, model_version=version,
                echo=f"[{name}]"))
    if telemetry.server is not None:
        telemetry.server.set_health_provider(service.health_snapshot)

    core = LocalReplica(name, service, watcher=watcher,
                        run_dir=results_folder)
    server = ReplicaServer(core, port=int(spec.get("port", 0)))

    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    signal.signal(signal.SIGINT, lambda *_: stop.set())

    import jax

    device = jax.devices()[0]
    ready = {"port": server.port, "pid": os.getpid(),
             "url": server.url(), "name": name,
             "device": {"platform": device.platform,
                        "kind": device.device_kind, "id": device.id}}
    tmp = spec["ready_file"] + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(ready, fh)
    os.replace(tmp, spec["ready_file"])
    threading.Thread(
        target=_heartbeat,
        args=(spec["ready_file"], stop,
              float(spec.get("heartbeat_s", 2.0))),
        daemon=True, name="ready-heartbeat").start()
    print(f"replica {name} serving on {server.url()}", flush=True)

    stop.wait()
    print(f"replica {name}: draining", flush=True)
    try:
        service.begin_drain()
        service.drain(float(spec.get("drain_timeout_s", 60.0)))
    finally:
        server.close()
        core.close()
        telemetry.finalize()
    return 0


if __name__ == "__main__":
    sys.exit(main())
