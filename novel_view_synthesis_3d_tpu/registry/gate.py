"""Quality gate: a version must not regress PSNR to reach `stable`.

The `latest` channel tracks training; `stable` is what production serves.
Between the two sits this gate: a FIXED-SEED PSNR probe (eval/metrics.py
math, a small respaced sampler) scored for the candidate AND the
incumbent stable version on the same conditioning batch and the same
noise, so the comparison isolates the weights. A candidate that regresses
beyond `registry.gate_margin_db` is refused — the stable pointer never
moves, a `gate_fail` row lands in the event log, and the operator's
rollback path (`nvs3d registry rollback`) stays one command away for
regressions the probe missed.

The probe is a tripwire, not a benchmark: a handful of rows at a few
reverse steps, sized to catch "the new checkpoint is broken" (NaN-poisoned
EMA, truncated payload, wrong lineage), not half-dB quality drift — the
full `eval` CLI remains the measurement instrument.

The probe scores candidates AT THE SERVING PRECISION
(`make_psnr_probe(precision=...)` = `serve.precision`): a bf16/int8
deployment's quantization loss is part of what ships, so it counts
against `registry.gate_margin_db` like any other regression.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

from novel_view_synthesis_3d_tpu.registry.store import (
    RegistryError,
    RegistryStore,
)

# event_cb(step, kind, detail, model_version) — the EventBus-routed hook
# (novel_view_synthesis_3d_tpu.obs) callers wire in; None = silent.
EventCb = Callable[[int, str, str, str], None]


@dataclasses.dataclass(frozen=True)
class GateResult:
    passed: bool
    candidate: str
    incumbent: Optional[str]
    candidate_psnr: float
    incumbent_psnr: Optional[float]
    margin_db: float
    reason: str

    @property
    def delta_db(self) -> Optional[float]:
        if self.incumbent_psnr is None:
            return None
        return self.candidate_psnr - self.incumbent_psnr


def decide(candidate_psnr: float, incumbent_psnr: Optional[float],
           margin_db: float) -> tuple:
    """(passed, reason) for a candidate-vs-incumbent PSNR pair.

    No incumbent = pass (first promotion bootstraps the channel). A
    non-finite candidate PSNR always fails — that is the broken-payload
    signature the gate exists for."""
    if candidate_psnr != candidate_psnr:  # NaN
        return False, "candidate probe PSNR is non-finite"
    if incumbent_psnr is None:
        return True, "no incumbent: bootstrap promotion"
    delta = candidate_psnr - incumbent_psnr
    if delta >= -margin_db:
        return True, (f"probe delta {delta:+.2f} dB within margin "
                      f"{margin_db:.2f} dB")
    return False, (f"probe regression {delta:+.2f} dB exceeds margin "
                   f"{margin_db:.2f} dB")


def make_psnr_probe(model, diffusion, batch: dict, *,
                    sample_steps: int, seed: int = 0,
                    precision: str = "float32"):
    """probe(params) -> mean PSNR (dB) of sampled vs ground-truth targets.

    One jitted sampler closure serves both the candidate and the
    incumbent (params are an argument, so scoring two versions costs zero
    extra compiles — the same property the serving hot-swap leans on),
    and the fixed key means both see bit-identical noise.

    `precision` stages BOTH versions' weights exactly the way the
    serving path would (sample/precision.py: bf16 cast / weight-only
    int8 quantize→dequantize) before scoring, so quantization loss
    counts against the gate margin — a candidate that only looks good
    in f32 cannot be promoted into a bf16/int8 deployment. Pass the
    deployment's `serve.precision` here (the CLI promote path does)."""
    from novel_view_synthesis_3d_tpu.models import require_family

    require_family(
        model.config, "xunet", "registry.gate.make_psnr_probe",
        "precision staging (sample/precision.py) of a token denoiser's "
        "tree: which leaves of an expert stack quantize, and a gate set "
        "scored at that precision")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from novel_view_synthesis_3d_tpu.diffusion.schedules import (
        sampling_schedule)
    from novel_view_synthesis_3d_tpu.eval.metrics import psnr
    from novel_view_synthesis_3d_tpu.sample import (
        precision as precision_lib)
    from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler

    precision_lib.validate_precision(precision)
    sampler = make_sampler(model, sampling_schedule(diffusion, sample_steps),
                           diffusion)
    cond = {k: jnp.asarray(batch[k])
            for k in ("x", "R1", "t1", "R2", "t2", "K")}
    truth = np.asarray(batch["target"])
    key = jax.random.PRNGKey(seed)

    def stage(params):
        staged = precision_lib.stage_params(params, precision)
        if precision == "int8":
            # Dequantize eagerly: the probe measures the NUMERICAL
            # effect of serving at int8 (the dequantized bf16 weights
            # are bit-identical to what the serving program computes
            # with), not the memory layout.
            staged = precision_lib.make_resolver("int8")(staged)
        return staged

    def probe(params) -> float:
        imgs = np.asarray(jax.device_get(
            sampler(stage(params), key, cond)))
        return float(np.mean(np.asarray(psnr(imgs, truth))))

    return probe


def make_trajectory_probe(model, diffusion, batch: dict, *,
                          frames: int, sample_steps: int, seed: int = 0,
                          precision: str = "float32",
                          k_max: Optional[int] = None):
    """probe(params) -> mean adjacent-frame PSNR (dB) over a fixed orbit.

    The multi-view CONSISTENCY tripwire
    (eval/metrics.multi_view_consistency): the candidate autoregressively
    renders a fixed-seed orbit with stochastic conditioning — each frame
    conditions on a random previously generated view, exactly the
    trajectory-serving workload — and is scored on how well adjacent
    frames agree. A distilled or quantized model whose SINGLE frames
    look fine but whose orbit drifts (the failure mode few-step
    students are prone to) regresses here, so pairing this probe with
    `make_psnr_probe` under the same `registry.gate_margin_db` gates
    promotions on trajectory quality, not just single-frame PSNR.
    Deterministic: fixed key, fixed orbit poses (camera radius taken
    from the probe batch), identical noise for candidate and incumbent.
    `precision` stages weights exactly like the serving path, as in
    `make_psnr_probe`."""
    from novel_view_synthesis_3d_tpu.models import require_family

    require_family(
        model.config, "xunet", "registry.gate.make_trajectory_probe",
        "precision staging (sample/precision.py) of a token denoiser's "
        "tree: which leaves of an expert stack quantize, and a gate set "
        "scored at that precision")
    import jax
    import numpy as np

    from novel_view_synthesis_3d_tpu.diffusion.schedules import (
        sampling_schedule)
    from novel_view_synthesis_3d_tpu.eval.metrics import adjacent_psnr
    from novel_view_synthesis_3d_tpu.sample import (
        precision as precision_lib)
    from novel_view_synthesis_3d_tpu.sample.ddpm import (
        autoregressive_generate, make_stochastic_sampler)
    from novel_view_synthesis_3d_tpu.utils.geometry import orbit_poses

    if frames < 2:
        raise ValueError(
            f"trajectory probe needs frames >= 2 (adjacent pairs), "
            f"got {frames}")
    precision_lib.validate_precision(precision)
    schedule = sampling_schedule(diffusion, sample_steps)
    first_view = {
        "x": np.asarray(batch["x"])[:1],
        "R1": np.asarray(batch["R1"])[:1],
        "t1": np.asarray(batch["t1"])[:1],
        "K": np.asarray(batch["K"])[:1],
    }
    radius = float(np.linalg.norm(first_view["t1"][0]))
    orbit = orbit_poses(frames, radius=radius or 1.0, elevation=0.3)
    target_poses = {
        "R2": np.asarray(orbit[None, :, :3, :3]),
        "t2": np.asarray(orbit[None, :, :3, 3]),
    }
    pool = max(2, k_max or (frames + 1))
    sampler = make_stochastic_sampler(model, schedule, diffusion,
                                      max_pool=pool)
    key = jax.random.PRNGKey(seed)

    def stage(params):
        staged = precision_lib.stage_params(params, precision)
        if precision == "int8":
            staged = precision_lib.make_resolver("int8")(staged)
        return staged

    def probe(params) -> float:
        imgs = autoregressive_generate(
            model, schedule, diffusion, stage(params), key, first_view,
            target_poses, max_pool=pool, sampler=sampler)
        imgs = np.asarray(jax.device_get(imgs))[0]  # (N, H, W, 3)
        return float(np.mean(np.asarray(adjacent_psnr(imgs))))

    return probe


def run_gate(store: RegistryStore, candidate_vid: str, *, channel: str,
             probe_fn: Callable, margin_db: float,
             event_cb: Optional[EventCb] = None,
             metric: str = "psnr") -> GateResult:
    """Score candidate vs the channel's incumbent; never moves pointers.

    The candidate payload is hash-verified on load, so a tampered or torn
    version fails here (IntegrityError) before any PSNR is computed.
    `metric` names the probe in the audit event (the trajectory-
    consistency gate runs through here too, with its own probe_fn)."""
    incumbent_vid = store.read_channel(channel)
    cand_manifest = store.verify(candidate_vid)
    candidate_params = store.load_params(candidate_vid, verify=False)
    candidate_psnr = probe_fn(candidate_params)
    incumbent_psnr = None
    if incumbent_vid and incumbent_vid != candidate_vid:
        incumbent_psnr = probe_fn(store.load_params(incumbent_vid))
    elif incumbent_vid == candidate_vid:
        incumbent_vid = None  # re-promoting the incumbent: bootstrap rule
    passed, reason = decide(candidate_psnr, incumbent_psnr, margin_db)
    result = GateResult(
        passed=passed, candidate=candidate_vid, incumbent=incumbent_vid,
        candidate_psnr=candidate_psnr, incumbent_psnr=incumbent_psnr,
        margin_db=margin_db, reason=reason)
    if event_cb is not None:
        inc = (f" vs incumbent {incumbent_vid} "
               f"{incumbent_psnr:.2f} dB" if incumbent_psnr is not None
               else "")
        event_cb(cand_manifest.step,
                 "gate_pass" if passed else "gate_fail",
                 f"channel {channel} [{metric}]: candidate "
                 f"{candidate_psnr:.2f} dB{inc}; {reason}", candidate_vid)
    return result


@dataclasses.dataclass(frozen=True)
class GateMatrixResult:
    """Per-(corpus × resolution) gate verdict (the ladder/mixer gate).

    `cells` rows: corpus, resolution, metric, candidate_psnr,
    incumbent_psnr, delta_db, passed, reason. The matrix passes only
    when EVERY cell passes — one regressed corpus or rung resolution
    blocks the promotion, margin-checked with the same decide() rule as
    the scalar gate."""

    passed: bool
    candidate: str
    incumbent: Optional[str]
    margin_db: float
    cells: tuple

    @property
    def worst(self) -> Optional[dict]:
        deltas = [c for c in self.cells if c["delta_db"] is not None]
        if not deltas:
            return None
        return min(deltas, key=lambda c: c["delta_db"])


def run_gate_matrix(store: RegistryStore, candidate_vid: str, *,
                    channel: str, cells, margin_db: float,
                    event_cb: Optional[EventCb] = None
                    ) -> GateMatrixResult:
    """Score candidate vs incumbent on EVERY (corpus, resolution) cell.

    `cells` is a sequence of dicts {corpus, resolution, metric,
    probe_fn} — cli._run_gates builds one per corpus of the mix × rung
    resolution of the ladder (registry item 5's eval matrix). Both
    versions are loaded ONCE and every probe scores the same trees, so
    an R×C matrix costs R·C probe runs, not R·C payload loads. Never
    moves pointers; emits one gate_pass/gate_fail audit event naming
    the worst cell."""
    incumbent_vid = store.read_channel(channel)
    cand_manifest = store.verify(candidate_vid)
    candidate_params = store.load_params(candidate_vid, verify=False)
    incumbent_params = None
    if incumbent_vid == candidate_vid:
        incumbent_vid = None  # re-promoting the incumbent: bootstrap rule
    elif incumbent_vid:
        incumbent_params = store.load_params(incumbent_vid)
    rows = []
    for cell in cells:
        cand = cell["probe_fn"](candidate_params)
        inc = (cell["probe_fn"](incumbent_params)
               if incumbent_params is not None else None)
        passed, reason = decide(cand, inc, margin_db)
        rows.append({
            "corpus": cell["corpus"],
            "resolution": int(cell["resolution"]),
            "metric": cell.get("metric", "psnr"),
            "candidate_psnr": cand,
            "incumbent_psnr": inc,
            "delta_db": None if inc is None else cand - inc,
            "passed": passed,
            "reason": reason,
        })
    result = GateMatrixResult(
        passed=all(r["passed"] for r in rows),
        candidate=candidate_vid, incumbent=incumbent_vid,
        margin_db=margin_db, cells=tuple(rows))
    if event_cb is not None:
        failed = [r for r in rows if not r["passed"]]
        worst = (min(failed, key=lambda r: r["delta_db"] or 0.0)
                 if failed else result.worst)
        detail = (f"channel {channel} matrix: {len(rows)} cells, "
                  f"{len(rows) - len(failed)} passed")
        if worst is not None:
            detail += (f"; worst {worst['corpus']}@{worst['resolution']}px"
                       f" [{worst['metric']}] {worst['candidate_psnr']:.2f}"
                       " dB" + (f" ({worst['delta_db']:+.2f} dB)"
                                if worst["delta_db"] is not None else ""))
        event_cb(cand_manifest.step,
                 "gate_pass" if result.passed else "gate_fail",
                 detail, candidate_vid)
    return result


def promote(store: RegistryStore, vid: str, *, channel: str = "stable",
            gate: Optional[GateResult] = None,
            event_cb: Optional[EventCb] = None) -> None:
    """Advance `channel` to `vid`. With a GateResult attached, a failed
    gate refuses the move (RegistryError) — auto-reject, pointer intact."""
    if gate is not None and not gate.passed:
        raise RegistryError(
            f"refusing to promote {vid} to {channel!r}: {gate.reason}")
    step = store.manifest(vid).step
    store.set_channel(channel, vid)
    if event_cb is not None:
        event_cb(step, "promote", f"channel {channel} -> {vid}", vid)


def rollback(store: RegistryStore, *, channel: str = "stable",
             event_cb: Optional[EventCb] = None) -> str:
    """Move `channel` back to its previous distinct version (the serving
    watcher picks the old weights up on its next poll)."""
    restored = store.rollback(channel)
    if event_cb is not None:
        event_cb(store.manifest(restored).step, "rollback",
                 f"channel {channel} rolled back to {restored}", restored)
    return restored
