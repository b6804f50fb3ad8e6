"""Sampling service: step-level continuous batching over a slot ring.

The ROADMAP north star is "serve heavy traffic from millions of users",
but until this module sampling was a one-shot CLI path: every request
shape compiled a fresh XLA program and requests ran one at a time at
batch sizes far below what keeps an accelerator's MXU fed. The reverse
process is 100s of UNet steps on a doubled-batch (CFG), so per-request
latency is dominated by device compute — exactly the regime where
micro-batching (torchgpipe, arXiv 2004.09910) and keeping the device fed
from the host side (MinatoLoader, arXiv 2509.10712) pay off.

Two schedulers share the front-end (serve.scheduler):

  - 'step' (default; docs/DESIGN.md "Continuous batching & distillation"):
    a persistent STEPPER — the diffusion analogue of LLM continuous
    batching. One compiled denoise-STEP program per bucket shape
    (sample/ddpm.make_ring_step_fn) runs over a ring of active request
    slots, each slot carrying its own (z, t, cond, keys, steps_remaining,
    model_version). New arrivals join the ring BETWEEN steps (filling
    padded slots), finished rows exit and respond immediately — a 4-step
    distilled request never waits behind a 256-step one. Heterogeneous
    per-row step counts and guidance weights ride in ONE batch: the
    schedule position t and w are device arguments (host-gathered by
    sample/stepper.ScheduleBank), never compile-time constants, so the
    program cache is keyed on bucket/shape only and a mixed 4/256-step
    warm sweep compiles nothing. Per-sample key threading makes each
    row's image bit-identical whether it stepped solo or interleaved
    with others joining/leaving mid-flight (ring-composition
    invariance, tests/test_stepper.py). A pending hot swap DRAINS the
    ring first: in-flight requests finish on their start version, queued
    arrivals ride the new one.

    TRAJECTORY SERVING rides the stepper (serve.k_max > 0; docs/DESIGN.md
    "Trajectory serving & stochastic conditioning"): `submit_trajectory`
    takes a source view plus an N-pose orbit, and the slot carries a
    device-resident FRAME BANK — (k_max, H, W, C) clean frames + poses.
    Each denoise step draws the row's conditioning view from its bank
    with the slot's PRNG carry (stochastic conditioning as an in-jit
    gather, 3DiM §3.2), a finished frame streams to the client AND is
    committed back into its own bank in-jit, and the next frame re-enters
    the ring without a host round-trip (fresh init noise via the `first`
    flag; the next pose rides the per-step device arguments). Because
    bank fill, pose, schedule, and guidance are all device arguments,
    mixed single-shot + trajectory traffic runs ONE program per bucket —
    and with serve.k_max=0 the stepper compiles the exact bank-free
    program, so single-shot serving is bit-identical to a build without
    trajectory support (zero-cost when unused). Hot swaps still drain
    the ring: an in-flight orbit finishes ALL frames on its start
    version (orbit consistency beats swap latency); the orbit deadline
    is re-checked at each frame's admission, and a mid-orbit expiry
    returns the completed frames in a structured TrajectoryExpired.
  - 'request': the PR 3 whole-request dispatcher (one lax.scan per
    coalesced same-program group), kept as the serve_bench baseline and
    for exact dpm++ 2M serving.

Shared architecture (docs/DESIGN.md "Serving"):

  - a BOUNDED request queue with backpressure: a submit past
    `serve.queue_depth` is rejected immediately with a reason (and an
    events.csv `reject` row — the trainer's fault-event convention)
    instead of growing tail latency without bound;
  - a worker thread COALESCES queued requests into one batch: it holds
    the oldest request open for `serve.flush_timeout_ms` so co-riders
    can join, up to `serve.max_batch`, and pads the group to the next
    power-of-two BUCKET size (pad rows are repeats of the last request
    and are sliced off the result — `make_request_sampler`'s per-sample
    RNG streams guarantee padding cannot change any request's image);
  - an LRU SAMPLER-PROGRAM CACHE keyed by (bucket, image size, k,
    sampler/steps/guidance config): warm traffic never recompiles, and
    the bucket ladder bounds the number of distinct programs to
    log2(max_batch)+1 per sampler config;
  - per-request DEADLINES: a request still queued past its deadline is
    rejected (deadline_exceeded) rather than served uselessly late;
  - SHARD-AWARE dispatch: when the service is built over a device mesh,
    buckets that divide the mesh 'data' axis dispatch through
    `parallel/mesh.shard_batch`, so a multi-chip mesh serves one
    coalesced batch data-parallel; ragged buckets dispatch replicated
    over the same mesh (params live on the mesh's device set, so this
    is the placement-compatible fallback — wasteful, never wrong);
  - instrumentation via `utils/profiling.ServiceStats`: per-request
    queue-wait / compile / device spans and a requests-per-second
    counter (tools/serve_bench.py reads these);
  - SERVING PRECISION (docs/DESIGN.md "Serving precision & fused
    kernels"): `serve.precision` decides what _stage_params puts on
    device — f32 as published, bf16 cast, or weight-only int8 with
    in-jit dequant (sample/precision.py) — for the initial weights AND
    every hot swap; the program-cache keys fold (precision, fused_step)
    in, and `diffusion.fused_step` routes the per-step update through
    the fused Pallas kernel (ops/fused_step.py) in both schedulers;
  - ZERO-DOWNTIME HOT RELOAD (docs/DESIGN.md "Model lifecycle"):
    `swap_params` stages a new param tree on the same placement (mesh
    replication or default device) ALONGSIDE the live one, and the
    worker thread flips the (params, model_version) reference BETWEEN
    dispatches — a dispatch in flight finishes on the version it
    started on, queued requests ride the new one. The sampler-program
    cache is keyed on shapes/config, not params, so every warm program
    survives the swap (zero recompiles — asserted by
    tools/serve_bench.py --hot-swap and tests/test_registry.py); the
    old tree's service-owned device buffers are freed after the flip.
    Every response and event row carries `model_version`; the
    registry's RegistryWatcher drives this from a channel pointer.
"""

from __future__ import annotations

import collections
import os
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from novel_view_synthesis_3d_tpu import obs
from novel_view_synthesis_3d_tpu.obs import reqtrace
from novel_view_synthesis_3d_tpu.obs import slo as slo_lib
from novel_view_synthesis_3d_tpu.utils import faultinject
from novel_view_synthesis_3d_tpu.config import DiffusionConfig, ServeConfig
from novel_view_synthesis_3d_tpu.diffusion.schedules import sampling_schedule
from novel_view_synthesis_3d_tpu.parallel import mesh as mesh_lib
from novel_view_synthesis_3d_tpu.ops.fused_step import resolve_fused_step
from novel_view_synthesis_3d_tpu.sample import precision as precision_lib
from novel_view_synthesis_3d_tpu.sample.ddpm import (
    make_bank_commit_fn,
    make_cond_encode_fn,
    make_request_sampler,
    make_ring_step_fn,
)
from novel_view_synthesis_3d_tpu.sample.stepper import FrameBank, ScheduleBank
from novel_view_synthesis_3d_tpu.utils.profiling import ServiceStats

COND_KEYS = ("x", "R1", "t1", "R2", "t2", "K")
# Conditioning a trajectory request must supply (its frames' target
# poses come from the pose list, not the cond dict).
TRAJ_COND_KEYS = ("x", "R1", "t1", "K")


class ServeError(RuntimeError):
    """Base class for request-level serving failures."""


class Rejected(ServeError):
    """Request refused at submit time (backpressure / bad input).

    The refusal is STRUCTURED (docs/DESIGN.md "Serving survivability"):
    `retryable=True` means the request itself was fine and the service
    was merely loaded/draining/restarting — clients should back off
    `retry_after_s` (plus jitter; cli.submit_with_retry) and resubmit.
    `retryable=False` (malformed conditioning, bad step count) means a
    retry would fail identically."""

    def __init__(self, message: str, *, retryable: bool = False,
                 retry_after_s: float = 0.0):
        super().__init__(message)
        self.retryable = retryable
        self.retry_after_s = float(retry_after_s)


class SampleAnomaly(ServeError):
    """A ring row's latent went non-finite and the slot was quarantined.

    The per-row finite mask (a device-side reduce folded into the step
    program, sample/ddpm.make_ring_step_fn) flagged this request's z;
    after `serve.anomaly_strikes` consecutive strikes the slot is
    EVICTED — its co-riders are untouched (ring-composition invariance
    means the poison cannot spread across rows) and nothing non-finite
    is ever streamed, resolved, or committed to a frame bank. Retryable:
    the usual causes (distilled/int8 students under guidance-weight
    extremes) are stochastic, so the same request often serves clean on
    resubmit. For trajectory tickets the frames already streamed ride
    along (`frames`); `frame_index` names the first frame NOT
    delivered."""

    retryable = True

    def __init__(self, message: str, *,
                 frames: Optional[List[np.ndarray]] = None,
                 frame_index: int = 0, retry_after_s: float = 0.0):
        super().__init__(message)
        self.frames = list(frames) if frames else []
        self.frame_index = int(frame_index)
        self.retry_after_s = float(retry_after_s)


class DeadlineExceeded(ServeError):
    """Request expired in the queue before dispatch."""


class TrajectoryExpired(DeadlineExceeded):
    """A trajectory request's deadline passed mid-orbit.

    Expiry is checked at each FRAME's admission (the frame boundary):
    frames already denoised were delivered on the ticket's stream and
    ride along here — the structured partial result — while
    `frame_index` names the first frame that was NOT generated."""

    def __init__(self, message: str, *, frames: List[np.ndarray],
                 frame_index: int):
        super().__init__(message)
        self.frames = frames
        self.frame_index = frame_index


def _normalize_poses(poses) -> tuple:
    """Trajectory pose list → ((N, 3, 3) R2, (N, 3) t2), loudly."""
    if isinstance(poses, dict):
        R = np.asarray(poses.get("R2"), np.float32)
        t = np.asarray(poses.get("t2"), np.float32)
    else:
        arr = np.asarray(poses, np.float32)
        if arr.ndim != 3 or arr.shape[-2:] != (4, 4):
            raise Rejected(
                "trajectory poses must be an (N, 4, 4) cam→world stack "
                f"or {{'R2': (N, 3, 3), 't2': (N, 3)}}; got shape "
                f"{arr.shape}")
        R, t = arr[:, :3, :3], arr[:, :3, 3]
    if (R.ndim != 3 or R.shape[-2:] != (3, 3)
            or t.shape != (R.shape[0], 3)):
        raise Rejected(
            f"trajectory poses malformed: R2 {R.shape}, t2 {t.shape} "
            "(want (N, 3, 3) and (N, 3))")
    return np.ascontiguousarray(R), np.ascontiguousarray(t)


def bucket_for(n: int, max_batch: int) -> int:
    """Smallest power-of-two bucket >= n, capped at max_batch."""
    if n < 1:
        raise ValueError(f"bucket_for: n={n} must be >= 1")
    b = 1
    while b < n:
        b *= 2
    return min(b, max_batch)


class Ticket:
    """Handle for one submitted request; `result()` blocks until served.

    `timing` (populated at resolution) carries the request's spans:
    queue_wait_s, device_s (or compile_s for the batch that warmed its
    program), plus the bucket and real batch size it rode in."""

    def __init__(self, request_id: int):
        self.request_id = request_id
        self.timing: dict = {}
        # Registry version the request was served on ("" pre-resolution
        # or for services constructed without one).
        self.model_version: str = ""
        self._done = threading.Event()
        self._image: Optional[np.ndarray] = None
        self._error: Optional[BaseException] = None

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"request {self.request_id} not served within {timeout}s")
        if self._error is not None:
            raise self._error
        return self._image

    # -- resolution (worker thread) ------------------------------------
    def _resolve(self, image: np.ndarray, timing: dict) -> None:
        self._image = image
        self.timing.update(timing)
        self._done.set()

    def _fail(self, error: BaseException) -> None:
        self._error = error
        self._done.set()


class TrajectoryTicket:
    """Handle for one trajectory request: frames STREAM as they complete.

    `frames()` yields (frame_index, image) in order, blocking until each
    is denoised — the client renders the orbit while later frames are
    still on device. `result()` blocks for the whole orbit and returns
    the stacked (N, H, W, 3) array. A mid-orbit deadline expiry raises
    `TrajectoryExpired` from both, carrying every completed frame."""

    def __init__(self, request_id: int, num_frames: int):
        self.request_id = request_id
        self.num_frames = num_frames
        self.timing: dict = {}
        self.model_version: str = ""
        self._done = threading.Event()
        self._lock = threading.Lock()
        self._frames: List[np.ndarray] = []
        self._frame_timing: List[dict] = []
        self._waiters: List[threading.Event] = []
        self._error: Optional[BaseException] = None

    def done(self) -> bool:
        return self._done.is_set()

    def frames_completed(self) -> int:
        with self._lock:
            return len(self._frames)

    def frames(self, timeout: Optional[float] = None):
        """Yield (frame_index, image) as each frame completes."""
        i = 0
        while i < self.num_frames:
            img = self._wait_frame(i, timeout)
            yield i, img
            i += 1

    def next_frame(self, index: int,
                   timeout: Optional[float] = None) -> np.ndarray:
        return self._wait_frame(index, timeout)

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"trajectory {self.request_id} not finished within "
                f"{timeout}s ({self.frames_completed()}/"
                f"{self.num_frames} frames)")
        if self._error is not None:
            raise self._error
        with self._lock:
            return np.stack(self._frames)

    # -- internals -----------------------------------------------------
    def _wait_frame(self, index: int, timeout: Optional[float]):
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            with self._lock:
                if index < len(self._frames):
                    return self._frames[index]
                if self._error is not None:
                    raise self._error
                if self._done.is_set():
                    raise ServeError(
                        f"trajectory {self.request_id} finished without "
                        f"frame {index}")
                ev = threading.Event()
                self._waiters.append(ev)
            left = (None if deadline is None
                    else max(0.0, deadline - time.monotonic()))
            if not ev.wait(left):
                raise TimeoutError(
                    f"frame {index} of trajectory {self.request_id} not "
                    f"served within {timeout}s")

    def _notify(self) -> None:
        for ev in self._waiters:
            ev.set()
        self._waiters.clear()

    # -- resolution (worker thread) ------------------------------------
    def _deliver(self, image: np.ndarray, timing: dict) -> None:
        with self._lock:
            self._frames.append(image)
            self._frame_timing.append(timing)
            self._notify()

    def _complete(self, timing: dict) -> None:
        self.timing.update(timing)
        with self._lock:
            self._notify()
        self._done.set()

    def _fail(self, error: BaseException) -> None:
        with self._lock:
            self._error = error
            self._notify()
        self._done.set()


class _Request:
    __slots__ = ("ticket", "cond", "key", "program_key", "t_submit",
                 "deadline_s", "trace_id", "swaps_at_submit",
                 "swap_drains", "rides", "responded")

    def __init__(self, ticket: Ticket, cond: Dict[str, np.ndarray],
                 key: np.ndarray, program_key: tuple, t_submit: float,
                 deadline_s: float):
        self.ticket = ticket
        self.cond = cond
        self.key = key
        self.program_key = program_key
        self.t_submit = t_submit
        self.deadline_s = deadline_s  # 0 = none
        # Request-scoped trace context (obs/reqtrace.py): the trace id
        # minted (or client-supplied) at submission, the swap counter
        # snapshot for swap-drain attribution, the number of ring
        # dispatches this request rode, and the responded latch (one
        # request_respond span per request, whatever path ends it).
        self.trace_id = ""
        self.swaps_at_submit = 0
        self.swap_drains = 0
        self.rides = 0
        self.responded = False

    @property
    def shape(self) -> tuple:
        return tuple(self.cond["x"].shape[:2])

    @property
    def is_traj(self) -> bool:
        return False


class _TrajRequest(_Request):
    """A trajectory request: N target poses, one frame bank, one slot."""

    __slots__ = ("poses_R", "poses_t", "k_cap")

    def __init__(self, ticket: TrajectoryTicket, cond, key, program_key,
                 t_submit, deadline_s, poses_R: np.ndarray,
                 poses_t: np.ndarray, k_cap: int):
        super().__init__(ticket, cond, key, program_key, t_submit,
                         deadline_s)
        self.poses_R = poses_R  # (N, 3, 3)
        self.poses_t = poses_t  # (N, 3)
        self.k_cap = k_cap

    @property
    def is_traj(self) -> bool:
        return True

    @property
    def num_frames(self) -> int:
        return int(self.poses_R.shape[0])


class _Slot:
    """One active request's ring state (step scheduler).

    Carries exactly what the tentpole contract names: the evolving latent
    `z` (host numpy between re-bucketings, device-resident on the carry
    fast path), the ladder position `t` (steps_remaining = t + 1), the
    conditioning (on the request), the per-row PRNG carry `keys`, and the
    model_version the row was admitted under (pinned: swaps drain the
    ring, so a slot never changes weights mid-flight)."""

    __slots__ = ("req", "bank", "w", "z", "keys", "first", "t", "version",
                 "t_admit", "device_s", "compile_s", "steps_done",
                 "bucket0", "batch0", "fbank", "frame_index", "frame_t0",
                 "strikes", "cc", "cc_bank")

    def __init__(self, req: _Request, bank, version: str, t_admit: float,
                 fbank: Optional[FrameBank] = None):
        self.req = req
        self.bank = bank
        self.w = float(req.program_key[3])
        self.z: Optional[np.ndarray] = None  # drawn on device at step 1
        self.keys = np.asarray(req.key, np.uint32)
        self.first = True
        self.t = bank.n - 1
        self.version = version
        self.t_admit = t_admit
        self.device_s = 0.0
        self.compile_s = 0.0
        self.steps_done = 0
        self.bucket0 = 0
        self.batch0 = 0
        # Trajectory state: the device-resident frame bank (None for
        # single-shot rows) and the index of the frame being denoised.
        self.fbank = fbank
        self.frame_index = 0
        self.frame_t0 = t_admit
        # Consecutive non-finite steps (the device-side anomaly mask);
        # at serve.anomaly_strikes the slot is quarantined.
        self.strikes = 0
        # Conditioning cache (serve.cond_cache): the admission-time
        # encode results, device-resident for the slot's lifetime and
        # pinned — like the weights — to the version the row was
        # admitted under (swaps drain the ring, so neither can change
        # mid-flight). `cc` is (pose_c tuple, feats_c) at B=1;
        # `cc_bank` is the per-bank-entry encode for trajectory rows
        # (re-encoded at each frame boundary against the next target
        # pose), None for single-shot rows.
        self.cc = None
        self.cc_bank = None

    @property
    def shape(self) -> tuple:
        return self.req.shape

    @property
    def is_traj(self) -> bool:
        return self.fbank is not None

    def target_pose(self) -> tuple:
        """(R2, t2) of the frame this slot is currently denoising."""
        if self.is_traj:
            return (self.req.poses_R[self.frame_index],
                    self.req.poses_t[self.frame_index])
        return self.req.cond["R2"], self.req.cond["t2"]


class SamplerProgramCache:
    """LRU of compiled request-sampler programs.

    Keyed by (bucket, H, W, steps, guidance, sampler, cfg_rescale,
    ddim_eta, objective, schedule, precision, fused_step) — see
    `SamplingService._cache_key`:
    everything that changes the XLA program a served batch runs.
    `builds` counts cache misses
    (each one is a retrace + compile); `jit_entries()` sums the live
    jitted functions' compiled-executable counts — the counter the
    zero-recompile-after-warmup assertion reads (tools/serve_bench.py,
    tests/test_serve.py)."""

    def __init__(self, factory: Callable[..., Callable], capacity: int,
                 on_build: Optional[Callable[[tuple, float], None]] = None):
        self._factory = factory
        self._capacity = max(1, capacity)
        self._entries: "collections.OrderedDict[tuple, dict]" = \
            collections.OrderedDict()
        self._lock = threading.Lock()
        self.builds = 0
        self.hits = 0
        # Build observer (the service's compile-ledger hook): called with
        # (key, trace wall seconds) for each factory build this cache
        # KEPT — raced duplicate builds are dropped unrecorded, matching
        # the `builds` counter the zero-recompile asserts read.
        self._on_build = on_build

    def get(self, key: tuple, *factory_args) -> dict:
        """Entry dict {fn, warm} for `key`, building (and evicting) as
        needed. `warm` flips True after the entry's first dispatch — the
        span-labeling bit (first call = compile span)."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                return entry
        t0 = time.perf_counter()
        fn = self._factory(*factory_args)
        build_s = time.perf_counter() - t0
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:  # raced another builder
                self._entries.move_to_end(key)
                self.hits += 1
                return entry
            entry = {"fn": fn, "warm": False}
            self._entries[key] = entry
            self.builds += 1
            while len(self._entries) > self._capacity:
                self._entries.popitem(last=False)
        if self._on_build is not None:
            try:
                self._on_build(key, build_s)
            except Exception:
                pass  # ledger bookkeeping must never fail a dispatch
        return entry

    def jit_entries(self) -> int:
        with self._lock:
            fns = [e["fn"] for e in self._entries.values()]
        total = 0
        for fn in fns:
            size = getattr(fn, "_cache_size", None)
            total += int(size()) if callable(size) else 1
        return total

    def counters(self) -> dict:
        with self._lock:
            n = len(self._entries)
            builds, hits = self.builds, self.hits
        return {"programs_built": builds, "cache_hits": hits,
                "programs_live": n, "jit_cache_entries": self.jit_entries()}

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


class SamplingService:
    """Micro-batching front-end over `make_request_sampler`.

    submit() is thread-safe and non-blocking (reject-on-full); a single
    worker thread batches, dispatches, and resolves tickets. One service
    instance serves ONE model + checkpoint; per-request knobs (seed,
    sample_steps, guidance_weight, deadline) ride on the request, and
    requests are only coalesced with others running the same program.
    """

    def __init__(self, model, params, diffusion: DiffusionConfig,
                 serve: Optional[ServeConfig] = None, *,
                 mesh=None, results_folder: Optional[str] = None,
                 start: bool = True, tracer=None, flight=None,
                 profiler=None, model_version: str = ""):
        from novel_view_synthesis_3d_tpu.models import require_family

        require_family(
            model.config, "xunet", "sample.service.SamplingService",
            "samplers and ring step functions that take the model's "
            "precompute seam (a latent cache per request or ring slot)")
        self.model = model
        self.diffusion = diffusion
        self.serve = serve or ServeConfig()
        self.mesh = mesh
        self._results_folder = results_folder or self.serve.results_folder
        # Flight recorder (obs/flight.py): always on. `nvs3d serve`
        # passes RunTelemetry's (whose bus tap already sees every span);
        # embedded/test use gets its own ring fed by _append_event and
        # the self-constructed tracer below.
        self.flight = (flight if flight is not None
                       else obs.FlightRecorder(self._results_folder))
        # Serving precision (sample/precision.py): how _stage_params
        # representations weights on device (f32 as-published / bf16
        # cast / weight-only int8 + in-jit dequant), folded into every
        # program-cache key. One service serves ONE precision — mixing
        # precisions means mixing model qualities mid-stream.
        self.precision = precision_lib.validate_precision(
            self.serve.precision)
        self._param_transform = precision_lib.make_resolver(self.precision)
        self.stats = ServiceStats()
        # Unified telemetry (obs/): the serving pipeline's spans
        # (queue_wait → batch_form → compile/device → respond) flow into
        # the shared registry's per-phase histogram — the same
        # /metrics surface the trainer feeds. `nvs3d serve` passes its
        # own tracer so trace.json lands next to the request PNGs;
        # embedded/test use gets a default one.
        self.tracer = tracer if tracer is not None else obs.Tracer(
            registry=obs.get_registry(), on_complete=self._flight_span)
        self._requests_total = obs.get_registry().counter(
            "nvs3d_requests_total", "requests served (resolved tickets)")
        self._rejects_total = obs.get_registry().counter(
            "nvs3d_rejects_total",
            "requests refused (backpressure, deadline)")
        self._model_swaps_total = obs.get_registry().counter(
            "nvs3d_model_swaps_total",
            "zero-downtime param swaps applied by the sampling service")
        self._model_version_gauge = obs.get_registry().gauge(
            "nvs3d_model_version",
            "live model version (label) and its training step (value)")
        # Trajectory serving gauges (docs/DESIGN.md "Trajectory serving
        # & stochastic conditioning").
        self._frames_total = obs.get_registry().counter(
            "nvs3d_frames_total",
            "trajectory frames denoised and streamed to clients")
        self._frames_per_sec = obs.get_registry().gauge(
            "nvs3d_frames_per_sec",
            "trajectory frame delivery rate since the first frame")
        self._traj_active = obs.get_registry().gauge(
            "nvs3d_trajectories_active",
            "trajectory requests currently holding a ring slot")
        self._frames_count = 0
        self._frames_t0: Optional[float] = None
        self._traj_in_ring = 0
        # Conditioning-cache telemetry (docs/DESIGN.md "Conditioning
        # cache & fused serving attention"): a hit is one ring row served
        # a step from cached activations, a miss is one encode-program
        # run (admission, uncond fill, or trajectory frame boundary).
        self._cond_hits_total = obs.get_registry().counter(
            "nvs3d_cond_cache_hits_total",
            "ring row-steps served from cached conditioning activations")
        self._cond_misses_total = obs.get_registry().counter(
            "nvs3d_cond_cache_misses_total",
            "conditioning encode runs (admissions, uncond fills, "
            "trajectory frame boundaries)")
        self._cond_resident_gauge = obs.get_registry().gauge(
            "nvs3d_cond_cache_resident_bytes",
            "device bytes held by cached conditioning activations "
            "(ring slots + the shared uncond cache)")
        # Survivability surfaces (docs/DESIGN.md "Serving
        # survivability"): anomaly quarantine, drain state, supervised
        # worker restarts, and the brownout ladder.
        self._anomalies_total = obs.get_registry().counter(
            "nvs3d_sample_anomalies_total",
            "ring rows quarantined for non-finite latents")
        self._worker_restarts_total = obs.get_registry().counter(
            "nvs3d_worker_restarts_total",
            "supervised restarts of the sampling worker thread")
        self._serve_state_gauge = obs.get_registry().gauge(
            "nvs3d_serve_state",
            "service lifecycle: 0=serving, 1=draining, 2=stopped")
        self._brownout_gauge = obs.get_registry().gauge(
            "nvs3d_brownout_level",
            "brownout ladder level: 0=serving, 1=degraded, 2=shedding")
        self._serve_state_gauge.set(0.0)
        self.anomalies = 0
        self.worker_restarts = 0
        self.dispatches = 0
        # Continuous profiler (obs/profiler.py, obs.profile.serve_*):
        # windows counted in dispatches, advanced on the worker thread
        # at each dispatch site. `nvs3d serve` passes one wired to its
        # RunTelemetry bus; embedded/test use defaults to None (off).
        self._profiler = profiler
        # Compile ledger (obs/compiles.py): every sampler-program build
        # lands in compiles.jsonl with a field-named fingerprint, so a
        # recompile names the knob that changed (bucket, steps, shape…) —
        # what serve_bench's zero-recompile asserts print as the culprit.
        self._compile_ledger = obs.CompileLedger(
            self._results_folder, registry=obs.get_registry())
        # /healthz progress heartbeat: stamped at every dispatch; a probe
        # reads last_dispatch_age_s to tell wedged-but-listening from
        # merely idle (pair it with queue depth).
        self._last_dispatch_t = time.time()
        self._draining = False
        self._drained_ev = threading.Event()
        self._brownout_level = 0
        self._ring_debt = 0
        self._events_lock = threading.Lock()
        # SLO engine (obs/slo.py): scores every finished request
        # against serve.slo.targets; None when no targets are declared.
        slo_cfg = self.serve.slo
        slo_targets = slo_lib.parse_targets(slo_cfg.targets)
        self.slo: Optional[slo_lib.SLOEngine] = None
        if slo_targets:
            self.slo = slo_lib.SLOEngine(
                targets=slo_targets, objective=slo_cfg.objective,
                fast_window_s=slo_cfg.fast_window_s,
                slow_window_s=slo_cfg.slow_window_s,
                fast_burn=slo_cfg.fast_burn,
                slow_burn=slo_cfg.slow_burn,
                registry=obs.get_registry(),
                event_cb=self._slo_event)
        # Live (params, model_version) pair — ONE attribute so readers
        # (the dispatch loop, _log_event) always see a consistent pair;
        # swaps stage a replacement and the worker flips it between
        # dispatches (_apply_pending_swap).
        staged, owned = self._stage_params(params)
        self._live = (staged, model_version)
        self._owned_ids = owned
        self._pending_swap: Optional[dict] = None
        self._swaps = 0
        if model_version:
            self._model_version_gauge.set(0.0, version=model_version)
        # Bucket ladder: powers of two up to max_batch; with a mesh, only
        # buckets the 'data' axis divides evenly are shard-dispatchable —
        # the others still serve, on the default device.
        self._buckets = []
        b = 1
        while b <= self.serve.max_batch:
            self._buckets.append(b)
            b *= 2
        # Trajectory serving (serve.k_max > 0): the stepper runs the
        # bank-enabled program so ring slots may carry a device-resident
        # frame bank. 0 keeps the EXACT bank-free program — trajectory
        # support is zero-cost (and bit-identical) when unused.
        self._k_max = int(self.serve.k_max)
        if self._k_max < 0:
            raise ValueError(f"serve.k_max={self.serve.k_max} must be >= 0")
        if self._k_max > 0 and self.serve.scheduler != "step":
            raise ValueError(
                f"serve.k_max={self._k_max} requires serve.scheduler="
                "'step' — trajectory frames re-enter the stepper ring "
                "between denoise steps (config.validate names the same "
                "constraint)")
        # Conditioning cache (serve.cond_cache; docs/DESIGN.md
        # "Conditioning cache & fused serving attention"): compute the
        # request's cond-branch activations ONCE at admission and feed
        # the step program device arguments instead of re-running rays →
        # posenc → per-level convs every denoise step.
        self._cond_cache = bool(self.serve.cond_cache)
        if self._cond_cache and self.serve.scheduler != "step":
            raise ValueError(
                "serve.cond_cache=True requires serve.scheduler='step' — "
                "the cache lives on stepper ring slots (config.validate "
                "names the same constraint)")
        if self.serve.scheduler == "step":
            # Stepper programs depend on bucket/shape ONLY (t, steps and
            # guidance ride as device args); the host-side coefficient
            # bank supplies per-row schedule values per dispatch.
            self._programs = SamplerProgramCache(
                self._build_step_program, self.serve.program_cache_entries,
                on_build=self._record_build)
            self._banks = ScheduleBank(self.diffusion)
            # Per-bucket all-False `first` vectors, staged once: the
            # carry fast path reuses them instead of re-uploading.
            self._false_cache: Dict[int, object] = {}
            # Zero frame banks for single-shot rows riding a bank-
            # enabled ring, staged once per (H, W) shape.
            self._zero_bank_cache: Dict[tuple, tuple] = {}
            # The in-jit frame commit program (one jitted callable;
            # XLA caches one executable per (k_max, H, W) shape).
            self._commit_fn = make_bank_commit_fn() if self._k_max else None
            # Admission-time conditioning encode (one jitted callable;
            # XLA caches one executable per (B, H, W) encode shape —
            # B=1 requests/uncond, B=k_max trajectory banks). The
            # per-(H, W) uncond cache is GLOBAL (the CFG uncond half is
            # pose- and image-independent — only conv biases + learned
            # embeddings survive the mask) and is invalidated on every
            # hot swap; per-request caches die with their ring slot.
            self._encode_fn = (make_cond_encode_fn(
                self.model, param_transform=self._param_transform)
                if self._cond_cache else None)
            self._uncond_cache: Dict[tuple, tuple] = {}
            self._zero_cc_cache: Dict[tuple, tuple] = {}
            self._encode_entries = 0
            self._cc_hits = 0
            self._cc_misses = 0
        else:
            self._programs = SamplerProgramCache(
                self._build_program, self.serve.program_cache_entries,
                on_build=self._record_build)
            self._banks = None
        self._lock = threading.Lock()
        self._queue_cv = threading.Condition(self._lock)
        self._queue: collections.deque = collections.deque()
        self._next_id = 0
        self._stop = threading.Event()
        self._worker: Optional[threading.Thread] = None
        if start:
            self.start()

    # -- lifecycle -----------------------------------------------------
    def start(self) -> "SamplingService":
        if self._worker is None:
            self._stop.clear()
            self._worker = threading.Thread(
                target=self._run_supervised, daemon=True,
                name="sampling-service")
            self._worker.start()
        return self

    def stop(self, timeout: Optional[float] = None) -> None:
        """Stop the worker; queued-but-undispatched requests fail with a
        RETRYABLE Rejected('service stopped').

        `timeout` (default serve.stop_timeout_s) bounds the worker join.
        A join that times out means the worker is WEDGED mid-dispatch —
        the service writes a stall-style all-thread-stacks diagnosis
        (stall_serve_stop_<n>.txt, the PR 2 watchdog convention) and
        raises instead of silently leaking a live thread that still owns
        the device."""
        timeout = self.serve.stop_timeout_s if timeout is None else timeout
        self._stop.set()
        with self._queue_cv:
            self._queue_cv.notify_all()
        worker = self._worker
        if worker is not None:
            worker.join(timeout=timeout)
            if worker.is_alive():
                self._dump_stop_stall(worker, timeout)
                raise RuntimeError(
                    f"sampling-service worker still alive after "
                    f"{timeout:.1f}s join (stop()): thread-stack "
                    f"diagnosis written under {self._results_folder!r} "
                    "(stall_serve_stop_*.txt)")
            self._worker = None
        if self._profiler is not None:
            # Close out a window left open mid-capture; the worker is
            # joined, so no dispatch races the stop_trace/parse.
            self._profiler.close()
        self._serve_state_gauge.set(2.0)
        # A swap staged but not yet applied must not leave its waiter
        # hanging: apply it inline (no dispatch can be in flight now).
        self._apply_pending_swap()
        self._fail_queue(lambda: Rejected(
            "service stopped", retryable=True, retry_after_s=1.0))

    def _fail_queue(self, make_error: Callable[[], ServeError]) -> None:
        with self._lock:
            leftovers = list(self._queue)
            self._queue.clear()
        for req in leftovers:
            req.ticket._fail(make_error())
            self._respond_span(req, "failed")

    def _dump_stop_stall(self, worker: threading.Thread,
                         timeout: float) -> None:
        """Wedged-worker diagnosis: every thread's stack to a stall_*
        file (stderr when even that fails — the diagnosis must never be
        the second fault), plus a `stall` event row."""
        from novel_view_synthesis_3d_tpu.utils import watchdog

        self._append_event(
            0, "stall",
            f"stop(): worker {worker.name!r} wedged past the "
            f"{timeout:.1f}s join (serve.stop_timeout_s); diagnosis "
            "stall_serve_stop_*.txt", model_version=self.model_version)
        self.flight.dump("stall", worker=worker.name,
                         timeout_s=timeout, dispatches=self.dispatches)
        body = (f"sampling-service stop(): worker {worker.name!r} still "
                f"alive after join timeout {timeout:.1f}s\n"
                f"time: {time.strftime('%Y-%m-%dT%H:%M:%SZ', time.gmtime())}"
                f"\ndispatches: {self.dispatches}\n\n"
                + watchdog.thread_stacks())
        try:
            os.makedirs(self._results_folder, exist_ok=True)
            n = 0
            while os.path.exists(os.path.join(
                    self._results_folder, f"stall_serve_stop_{n}.txt")):
                n += 1
            path = os.path.join(self._results_folder,
                                f"stall_serve_stop_{n}.txt")
            with open(path, "w") as fh:
                fh.write(body)
            print(f"[serve] wedged-worker diagnosis: {path}",
                  file=sys.stderr, flush=True)
        except OSError:
            print(body, file=sys.stderr, flush=True)

    def begin_drain(self, reason: str = "") -> None:
        """Flip to DRAINING: admissions are rejected with a structured
        retryable reason; queued + in-ring work keeps being served until
        done (the worker then parks itself). Non-blocking — `drain()`
        adds the wait + stop. Idempotent."""
        with self._queue_cv:
            if self._draining or self._stop.is_set():
                return
            self._draining = True
            self._queue_cv.notify_all()
        self._serve_state_gauge.set(1.0)
        self._append_event(
            0, "drain",
            "accepting -> draining"
            + (f" ({reason})" if reason else "")
            + "; new admissions rejected retryably, in-flight work "
            f"finishes within serve.drain_timeout_s="
            f"{self.serve.drain_timeout_s:.0f}s",
            model_version=self.model_version)

    def drain(self, timeout_s: Optional[float] = None,
              reason: str = "") -> bool:
        """Graceful shutdown (the SIGTERM path of `nvs3d serve`):
        reject new admissions retryably, let every queued and in-ring
        request finish, then stop. Returns True when everything in
        flight completed within `timeout_s` (default
        serve.drain_timeout_s); on timeout the leftovers fail with a
        retryable Rejected via stop()."""
        timeout_s = (self.serve.drain_timeout_s if timeout_s is None
                     else float(timeout_s))
        self.begin_drain(reason)
        worker = self._worker
        if worker is None or not worker.is_alive():
            with self._lock:
                drained = not self._queue
        else:
            drained = self._drained_ev.wait(timeout_s)
        self._append_event(
            0, "drain",
            ("draining -> stopped (clean: queue and ring empty)"
             if drained else
             f"draining -> stopped (TIMEOUT after {timeout_s:.1f}s; "
             "leftover requests fail retryably)"),
            model_version=self.model_version)
        if not drained:
            self.flight.dump("drain_timeout", timeout_s=timeout_s,
                             dispatches=self.dispatches)
        self.stop()
        return drained

    def __enter__(self) -> "SamplingService":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- params lifecycle (zero-downtime hot reload) -------------------
    @property
    def params(self):
        return self._live[0]

    @property
    def model_version(self) -> str:
        return self._live[1]

    def _stage_params(self, params):
        """Stage a param tree at the serving precision and place it
        where dispatch needs it (mesh-replicated or default device).

        Precision staging happens ON HOST first (sample/precision.py):
        bf16 casts / int8 quantization produce a fresh host tree, so the
        device upload ships the small representation and the weights
        REST on device at serving precision. float32 stages the caller's
        tree unchanged (bit-exact legacy path).

        Returns (staged_tree, owned_leaf_ids): only buffers UPLOADED
        HERE from host (numpy) leaves count as service-owned — the ones
        a later swap may free. A device-array input may come back from
        device_put as a NEW wrapper over the SAME buffer, so deleting by
        object identity would kill the caller's tree; those leaves are
        left to garbage collection instead. (At bf16/int8 every staged
        leaf is a derived host copy, so the service owns them all.)"""
        params = precision_lib.stage_params(params, self.precision)
        if self.mesh is not None:
            staged = mesh_lib.replicate(self.mesh, params)
        else:
            staged = jax.device_put(params, jax.devices()[0])
        owned = set()
        for inp, out in zip(jax.tree.leaves(params),
                            jax.tree.leaves(staged)):
            if not isinstance(inp, jax.Array) and out is not inp \
                    and hasattr(out, "delete"):
                owned.add(id(out))
        return staged, owned

    def _free_tree(self, tree, owned_ids, keep_ids=frozenset()) -> None:
        for leaf in jax.tree.leaves(tree):
            if (id(leaf) in owned_ids and id(leaf) not in keep_ids
                    and hasattr(leaf, "delete")):
                try:
                    leaf.delete()
                except Exception:
                    pass  # already deleted / non-owning view

    def swap_params(self, params, version: str, *,
                    step: Optional[int] = None,
                    timeout: Optional[float] = None) -> threading.Event:
        """Stage `params` alongside the live set and request a swap.

        The upload happens HERE (and is waited on), so the flip itself —
        applied by the worker between dispatches — is a reference
        assignment: no request ever blocks on a host→device transfer of
        the new weights. Requests in flight finish on the version they
        started on; every later dispatch serves `version`. Warm sampler
        programs survive (the cache key has no params in it).

        Returns the 'applied' event; `timeout` (seconds) waits for it —
        with an idle or stopped worker the swap is applied inline.
        """
        staged, owned = self._stage_params(params)
        jax.block_until_ready(staged)
        applied = threading.Event()
        pend = {"params": staged, "owned": owned, "version": version,
                "step": step, "applied": applied}
        with self._queue_cv:
            prev, self._pending_swap = self._pending_swap, pend
            self._queue_cv.notify_all()
        if prev is not None:
            # Superseded before it ever served: free its staging copy and
            # release anyone waiting on it (last writer wins).
            self._free_tree(prev["params"], prev["owned"],
                            keep_ids={id(l) for l in
                                      jax.tree.leaves(staged)})
            prev["applied"].set()
        if self._worker is None or not self._worker.is_alive():
            self._apply_pending_swap()
        if timeout is not None:
            applied.wait(timeout)
        return applied

    def _apply_pending_swap(self) -> None:
        """Flip to a staged param set; runs on the worker thread between
        dispatches (or inline when no worker is running), so no dispatch
        holds the old tree when its buffers are freed."""
        with self._queue_cv:
            pend, self._pending_swap = self._pending_swap, None
        if pend is None:
            return
        old, old_version = self._live
        with self.tracer.span("model_swap", version=pend["version"],
                              prev=old_version or "<initial>"):
            self._live = (pend["params"], pend["version"])
            self._free_tree(
                old, self._owned_ids,
                keep_ids={id(l) for l in jax.tree.leaves(pend["params"])})
            self._owned_ids = pend["owned"]
            # Conditioning-cache invalidation: the shared uncond halves
            # were encoded through the OLD weights. Per-request caches
            # need no action — the drain-on-swap contract means no ring
            # slot is alive here, so every in-flight row stayed pinned
            # to the activations (and weights) of its start version.
            if self._cond_cache:
                self._uncond_cache.clear()
        self._swaps += 1
        self._model_swaps_total.inc()
        self._model_version_gauge.set(
            float(pend["step"]) if pend["step"] is not None
            else float(self._swaps), version=pend["version"])
        self._append_event(
            pend["step"] or 0, "model_swap",
            f"{old_version or '<initial>'} -> {pend['version']} "
            f"(swap {self._swaps}, {len(self._programs)} warm programs "
            "kept)", model_version=pend["version"])
        pend["applied"].set()

    # -- submission ----------------------------------------------------
    def _step_debt_locked(self) -> int:
        """Denoise steps still owed: the ring's remaining steps (updated
        by the worker each dispatch) plus everything queued. One of the
        two brownout pressure signals — queue DEPTH is blind to a queue
        of three 256-step orbits. Caller holds self._lock."""
        debt = self._ring_debt
        for r in self._queue:
            steps = int(r.program_key[2])
            debt += steps * (r.num_frames if r.is_traj else 1)
        return debt

    def _brownout_check(self, request_id: int) -> int:
        """Evaluate the brownout ladder at admission time; returns the
        level (0 serving / 1 degraded / 2 shedding) and logs + gauges
        the transition when it moved."""
        bo = self.serve.brownout
        if not (bo.queue_soft or bo.queue_hard or bo.debt_soft
                or bo.debt_hard):
            return 0
        with self._lock:
            q = len(self._queue)
            debt = (self._step_debt_locked()
                    if (bo.debt_soft or bo.debt_hard) else 0)
            level = 0
            if ((bo.queue_soft and q >= bo.queue_soft)
                    or (bo.debt_soft and debt >= bo.debt_soft)):
                level = 1
            if ((bo.queue_hard and q >= bo.queue_hard)
                    or (bo.debt_hard and debt >= bo.debt_hard)):
                level = 2
            prev, self._brownout_level = self._brownout_level, level
        if level != prev:
            self._brownout_gauge.set(float(level))
            names = {0: "serving", 1: "degraded", 2: "shedding"}
            self._append_event(
                request_id, "brownout",
                f"level {prev} ({names[prev]}) -> {level} "
                f"({names[level]}): queued={q}, step_debt={debt}",
                model_version=self.model_version)
        return level

    def _reject_drain(self, ticket) -> None:
        self._log_event(ticket.request_id, "drain",
                        "admission rejected: service draining "
                        "(retryable)")
        raise Rejected(
            "service draining for restart; retry against a peer or "
            "after the restart", retryable=True,
            retry_after_s=self.serve.drain_timeout_s)

    def submit(self, cond: Dict[str, np.ndarray], *, seed: int = 0,
               sample_steps: Optional[int] = None,
               guidance_weight: Optional[float] = None,
               deadline_ms: Optional[float] = None,
               trace_id: Optional[str] = None) -> Ticket:
        """Enqueue one request; returns immediately with a Ticket.

        `cond` holds UNBATCHED conditioning: x (H, W, 3), R1/R2 (3, 3),
        t1/t2 (3,), K (3, 3) — the service stacks requests into the
        batch axis. Raises Rejected when the queue is full (the events
        log records why), or on malformed conditioning. `trace_id`
        names the request's trace (obs/reqtrace.py; sanitized);
        default: minted from the request id.
        """
        missing = [k for k in COND_KEYS if k not in cond]
        if missing:
            raise Rejected(f"request missing conditioning keys {missing}")
        x = np.asarray(cond["x"])
        if x.ndim != 3:
            raise Rejected(
                f"cond['x'] must be unbatched (H, W, 3); got {x.shape}")
        steps = sample_steps or self.serve.sample_steps or \
            self.diffusion.sample_timesteps
        if not 1 <= int(steps) <= self.diffusion.timesteps:
            raise Rejected(
                f"sample_steps={steps} outside [1, diffusion.timesteps="
                f"{self.diffusion.timesteps}]")
        w = (self.diffusion.guidance_weight
             if guidance_weight is None else float(guidance_weight))
        if deadline_ms is None:
            deadline_ms = self.serve.default_deadline_ms
        program_key = (int(x.shape[0]), int(x.shape[1]), int(steps), w)
        ticket = Ticket(self._claim_id())
        level = self._brownout_check(ticket.request_id)
        if level >= 2:
            self._log_event(
                ticket.request_id, "reject",
                "brownout shed (level 2): load above "
                "serve.brownout.{queue,debt}_hard (retryable)")
            raise Rejected(
                "service shedding load (brownout level 2); retry with "
                "backoff", retryable=True,
                retry_after_s=self.serve.brownout.retry_after_s)
        req = _Request(
            ticket,
            {k: np.asarray(cond[k]) for k in COND_KEYS},
            np.asarray(jax.random.PRNGKey(seed)),
            program_key, time.monotonic(),
            float(deadline_ms) / 1000.0 if deadline_ms else 0.0)
        req.trace_id = reqtrace.mint(ticket.request_id, trace_id)
        req.swaps_at_submit = self._swaps
        with self._queue_cv:
            if self._stop.is_set():
                raise Rejected("service stopped")
            if self._draining:
                self._reject_drain(ticket)
            if len(self._queue) >= self.serve.queue_depth:
                self._log_event(
                    ticket.request_id, "reject",
                    f"queue full (depth {self.serve.queue_depth})")
                raise Rejected(
                    f"queue full (serve.queue_depth="
                    f"{self.serve.queue_depth}); retry with backoff",
                    retryable=True, retry_after_s=0.05)
            self._queue.append(req)
            self._queue_cv.notify_all()
        self._submit_span(req, "single", int(steps), level)
        return ticket

    def _submit_span(self, req: _Request, req_kind: str, steps: int,
                     brownout_level: int,
                     frames: Optional[int] = None) -> None:
        """The trace root (obs/reqtrace.py contract): a zero-duration
        request_submit marker carrying the span_id every request-scoped
        child points back at. Emitted AFTER the enqueue commits —
        rejected submissions have no trace."""
        attrs = dict(trace_id=req.trace_id,
                     span_id=reqtrace.root_span_id(req.trace_id),
                     request_id=req.ticket.request_id,
                     req_kind=req_kind, steps=steps,
                     brownout=brownout_level)
        if frames is not None:
            attrs["frames"] = int(frames)
        self.tracer.add_span("request_submit", 0.0, **attrs)

    def submit_trajectory(self, cond: Dict[str, np.ndarray], *,
                          poses, seed: int = 0,
                          sample_steps: Optional[int] = None,
                          guidance_weight: Optional[float] = None,
                          deadline_ms: Optional[float] = None,
                          k_max: Optional[int] = None,
                          trace_id: Optional[str] = None
                          ) -> TrajectoryTicket:
        """Enqueue one N-frame trajectory; returns a streaming ticket.

        `cond` holds the UNBATCHED source view: x (H, W, 3), R1 (3, 3),
        t1 (3,), K (3, 3). `poses` is the orbit — an (N, 4, 4) cam→world
        pose stack or a dict {"R2": (N, 3, 3), "t2": (N, 3)}. Each frame
        runs `sample_steps` denoise steps; every step conditions on a
        bank view per diffusion.stochastic_cond, and each finished frame
        is committed into the bank in-jit before the next re-enters the
        ring — the whole orbit stays device-resident. `k_max` bounds
        this request's sliding conditioning window (default, and upper
        bound: serve.k_max). `deadline_ms` covers the WHOLE orbit and is
        re-checked at each frame's admission; a mid-orbit expiry
        delivers the completed frames inside a TrajectoryExpired."""
        if self.serve.scheduler != "step" or self._k_max < 1:
            raise Rejected(
                "trajectory serving is disabled: it needs serve."
                "scheduler='step' and serve.k_max > 0 (got scheduler="
                f"{self.serve.scheduler!r}, k_max={self.serve.k_max}) — "
                "the frame bank is sized at service construction")
        missing = [k for k in TRAJ_COND_KEYS if k not in cond]
        if missing:
            raise Rejected(
                f"trajectory request missing conditioning keys {missing}")
        x = np.asarray(cond["x"])
        if x.ndim != 3:
            raise Rejected(
                f"cond['x'] must be unbatched (H, W, 3); got {x.shape}")
        poses_R, poses_t = _normalize_poses(poses)
        n_frames = poses_R.shape[0]
        if not 1 <= n_frames <= self.serve.max_frames:
            raise Rejected(
                f"trajectory has {n_frames} poses; serve.max_frames="
                f"{self.serve.max_frames} bounds a request (split the "
                "orbit, or raise serve.max_frames)")
        cap = self._k_max if k_max is None else int(k_max)
        if not 1 <= cap <= self._k_max:
            raise Rejected(
                f"k_max={k_max} outside [1, serve.k_max={self._k_max}] — "
                "the service's bank arrays are sized once; per-request "
                "windows can only shrink")
        ticket_id = self._claim_id()
        level = self._brownout_check(ticket_id)
        if level >= 2:
            self._log_event(
                ticket_id, "reject",
                "brownout shed (level 2): load above "
                "serve.brownout.{queue,debt}_hard (retryable)")
            raise Rejected(
                "service shedding load (brownout level 2); retry with "
                "backoff", retryable=True,
                retry_after_s=self.serve.brownout.retry_after_s)
        if level == 1:
            # Degraded admission: cheaper orbits instead of refusal —
            # a narrower conditioning window and/or a truncated pose
            # list, applied HERE so an in-flight orbit never changes
            # shape mid-ring.
            bo = self.serve.brownout
            if bo.k_cap and cap > bo.k_cap:
                cap = bo.k_cap
            if bo.max_frames_cap and n_frames > bo.max_frames_cap:
                poses_R = poses_R[:bo.max_frames_cap]
                poses_t = poses_t[:bo.max_frames_cap]
                n_frames = bo.max_frames_cap
                self._log_event(
                    ticket_id, "brownout",
                    f"degraded admission (level 1): orbit capped to "
                    f"{n_frames} frames, bank window {cap}")
        steps = sample_steps or self.serve.sample_steps or \
            self.diffusion.sample_timesteps
        if not 1 <= int(steps) <= self.diffusion.timesteps:
            raise Rejected(
                f"sample_steps={steps} outside [1, diffusion.timesteps="
                f"{self.diffusion.timesteps}]")
        w = (self.diffusion.guidance_weight
             if guidance_weight is None else float(guidance_weight))
        if deadline_ms is None:
            deadline_ms = self.serve.default_deadline_ms
        program_key = (int(x.shape[0]), int(x.shape[1]), int(steps), w)
        ticket = TrajectoryTicket(ticket_id, n_frames)
        full_cond = {k: np.asarray(cond[k]) for k in TRAJ_COND_KEYS}
        # R2/t2 ride as zeros so trajectory rows stack uniformly with
        # single-shot rows; the step program takes the CURRENT frame's
        # pose from the per-step device arguments instead.
        full_cond["R2"] = np.zeros((3, 3), np.float32)
        full_cond["t2"] = np.zeros((3,), np.float32)
        req = _TrajRequest(
            ticket, full_cond, np.asarray(jax.random.PRNGKey(seed)),
            program_key, time.monotonic(),
            float(deadline_ms) / 1000.0 if deadline_ms else 0.0,
            poses_R, poses_t, cap)
        req.trace_id = reqtrace.mint(ticket_id, trace_id)
        req.swaps_at_submit = self._swaps
        with self._queue_cv:
            if self._stop.is_set():
                raise Rejected("service stopped")
            if self._draining:
                self._reject_drain(ticket)
            if len(self._queue) >= self.serve.queue_depth:
                self._log_event(
                    ticket.request_id, "reject",
                    f"queue full (depth {self.serve.queue_depth})")
                raise Rejected(
                    f"queue full (serve.queue_depth="
                    f"{self.serve.queue_depth}); retry with backoff",
                    retryable=True, retry_after_s=0.05)
            self._queue.append(req)
            self._queue_cv.notify_all()
        self._submit_span(req, "trajectory", int(steps), level,
                          frames=n_frames)
        return ticket

    def _claim_id(self) -> int:
        with self._lock:
            self._next_id += 1
            return self._next_id

    # -- observability -------------------------------------------------
    def compile_counters(self) -> dict:
        counters = self._programs.counters()
        commit_fn = getattr(self, "_commit_fn", None)
        if commit_fn is not None:
            # The in-jit bank-commit program compiles once per
            # (k_max, H, W) shape; its executables count here so the
            # zero-recompile asserts cover the trajectory path too.
            size = getattr(commit_fn, "_cache_size", None)
            counters["commit_jit_entries"] = (
                int(size()) if callable(size) else 0)
        encode_fn = getattr(self, "_encode_fn", None)
        if encode_fn is not None:
            # The admission-time cond-encode program compiles once per
            # (B, H, W) encode shape; counting its executables here puts
            # it under the same zero-recompile asserts as the step and
            # commit programs (mixed cached/uncached warm traffic must
            # compile nothing).
            size = getattr(encode_fn, "_cache_size", None)
            counters["encode_jit_entries"] = (
                int(size()) if callable(size) else 0)
        return counters

    def summary(self) -> dict:
        try:
            fused = resolve_fused_step(self.diffusion.fused_step)
        except ValueError:
            fused = self.diffusion.fused_step
        out = dict(self.stats.summary(), **self.compile_counters(),
                   model_version=self.model_version,
                   model_swaps=self._swaps,
                   precision=self.precision, fused_step=fused,
                   anomalies=self.anomalies,
                   worker_restarts=self.worker_restarts,
                   brownout_level=self._brownout_level,
                   flight_dumps=len(self.flight.dumps))
        if self._banks is not None:
            out["schedule_bank"] = self._banks.counters()
        if self._cond_cache:
            out["cond_cache"] = self._cond_cache_stats()
        if self.slo is not None:
            out["slo"] = self.slo.snapshot()
        return out

    def _cond_cache_stats(self) -> dict:
        hits, misses = self._cc_hits, self._cc_misses
        total = hits + misses
        return {
            "enabled": True,
            "hits": hits,
            "misses": misses,
            "hit_rate": (hits / total) if total else 0.0,
            "uncond_entries": len(self._uncond_cache),
            "resident_bytes": int(
                self._cond_resident_gauge.value() or 0),
        }

    def _log_event(self, request_id: int, kind: str, detail: str) -> None:
        """Event-log append via the obs bus, schema-compatible with the
        trainer's MetricsLogger.log_event (request id in the step
        column). Rare by construction (rejections and expiries)."""
        self._rejects_total.inc(kind=kind)
        self._append_event(request_id, kind, detail,
                           model_version=self.model_version)

    def _append_event(self, step: int, kind: str, detail: str, *,
                      model_version: str = "") -> None:
        # Events also land in the flight ring, so a dump's tail holds
        # the event that triggered it (anomaly/restart/drain/stall).
        self.flight.note("event", step=step, event=kind, detail=detail,
                         model_version=model_version)
        try:
            with self._events_lock:
                obs.append_event(self._results_folder, step, kind,
                                 detail, model_version=model_version)
        except OSError:
            pass  # the event log must never be the serving fault

    def _flight_span(self, rec: dict) -> None:
        """on_complete sink for the self-constructed tracer: flatten a
        span record into the flight ring (the bus.span_record shape,
        minus the JSONL file). `nvs3d serve` doesn't use this — its
        tracer feeds RunTelemetry's bus, whose tap IS the recorder."""
        self.flight.record(
            {"kind": "span", "name": rec["name"],
             "dur_s": round(rec["dur"], 6),
             **{k: v for k, v in rec.get("attrs", {}).items()
                if isinstance(v, (int, float, str, bool))}})

    def _slo_event(self, kind: str, detail: str) -> None:
        self._append_event(0, kind, detail,
                           model_version=self.model_version)

    def _respond_span(self, req: _Request, outcome: str, *,
                      steps_done: int = 0,
                      frames_done: Optional[int] = None) -> None:
        """Close a request's trace: ONE request_respond span covering
        submit→now, whatever path ended it (resolution, anomaly,
        expiry, worker failure), plus the SLO sample. Idempotent per
        request — the first closer wins (a quarantined slot must not be
        re-closed by a later ring unwind)."""
        if req.responded or not req.trace_id:
            req.responded = True
            return
        req.responded = True
        latency = max(0.0, time.monotonic() - req.t_submit)
        attrs = dict(
            trace_id=req.trace_id,
            parent_id=reqtrace.root_span_id(req.trace_id),
            request_id=req.ticket.request_id,
            outcome=outcome,
            latency_s=round(latency, 6),
            steps=int(req.program_key[2]),
            steps_done=int(steps_done),
            dispatches=req.rides,
            swap_drains=req.swap_drains,
            model_version=self.model_version)
        if frames_done is not None:
            attrs["frames_done"] = int(frames_done)
        self.tracer.add_span("request_respond", latency, **attrs)
        if self.slo is not None:
            self.slo.record(int(req.program_key[2]), latency,
                            ok=(outcome == "ok"))

    # -- batching worker -----------------------------------------------
    def _run_supervised(self) -> None:
        """Worker supervisor (the serving analogue of train/supervisor):
        a worker death — anything escaping `_run`'s per-dispatch guards
        — is restarted with bounded exponential backoff instead of
        stranding every ticket. Undispatched requests STAY QUEUED across
        the restart (the new worker admits them); in-flight ring rows
        were already failed retryably by `_run_stepper`'s unwind. Past
        serve.max_worker_restarts the service gives up loudly: the
        queue fails retryably and the service stops."""
        while True:
            try:
                self._run()
                return  # clean exit: stop() or drain completion
            except BaseException as exc:
                if self._stop.is_set():
                    return
                self.worker_restarts += 1
                self._worker_restarts_total.inc()
                n = self.worker_restarts
                budget = self.serve.max_worker_restarts
                if n > budget:
                    self._append_event(
                        -1, "worker_restart",
                        f"worker died ({exc!r}); restart budget "
                        f"serve.max_worker_restarts={budget} exhausted "
                        "— service stopping, queued requests fail "
                        "retryably", model_version=self.model_version)
                    print(f"[serve] worker died ({exc!r}); restart "
                          f"budget {budget} exhausted — stopping",
                          file=sys.stderr, flush=True)
                    self.flight.dump("worker_restart", restart=n,
                                     budget=budget, exhausted=True)
                    self._stop.set()
                    self._fail_queue(lambda: Rejected(
                        "service worker dead (restart budget "
                        "exhausted); retry against a peer",
                        retryable=True, retry_after_s=1.0))
                    return
                delay = min(30.0, self.serve.worker_backoff_s
                            * (2 ** (n - 1)))
                self._append_event(
                    -1, "worker_restart",
                    f"worker died ({exc!r}); supervised restart "
                    f"{n}/{budget} in {delay:.2f}s — undispatched "
                    "requests stay queued",
                    model_version=self.model_version)
                self.flight.dump("worker_restart", restart=n,
                                 budget=budget, exhausted=False)
                if delay > 0 and self._stop.wait(delay):
                    return

    def _run(self) -> None:
        if self.serve.scheduler == "step":
            self._run_stepper()
        else:
            self._run_request()

    def _run_request(self) -> None:
        """Whole-request dispatch (PR 3 semantics; serve.scheduler=
        'request'): one lax.scan program per coalesced group."""
        while not self._stop.is_set():
            faultinject.maybe_serve_worker_die(self.dispatches)
            with self._lock:
                if self._draining and not self._queue:
                    break  # drained: nothing queued, nothing in flight
            # Swaps apply HERE — between dispatches, never under one, so
            # freeing the old tree can't race an in-flight program.
            self._apply_pending_swap()
            group = self._collect_group()
            if not group:
                continue
            try:
                self._dispatch(group)
            except BaseException as exc:  # resolve, don't kill the worker
                for req in group:
                    req.ticket._fail(
                        ServeError(f"dispatch failed: {exc!r}"))
                    self._respond_span(req, "failed")
        self._drained_ev.set()

    # -- step-level continuous batching (serve.scheduler='step') --------
    def _run_stepper(self) -> None:
        """Persistent stepper: a ring of active slots advances one
        denoise step per dispatch; arrivals join between steps, finished
        rows exit immediately. `carry` keeps the ring's (z, keys, cond)
        device-resident while the composition is stable — the common
        no-join/no-exit iteration moves nothing through the host."""
        ring: List[_Slot] = []
        carry: Optional[dict] = None
        try:
            while not self._stop.is_set():
                # Worker-death drill: raises OUTSIDE the per-dispatch
                # guard below, so the exception unwinds the thread and
                # exercises the supervisor restart path.
                faultinject.maybe_serve_worker_die(self.dispatches)
                if not ring:
                    self._ring_debt = 0
                    with self._lock:
                        if self._draining and not self._queue:
                            break  # drained: ring and queue both empty
                    # Swaps apply only on an empty ring (drain-on-swap):
                    # in-flight requests keep their start version.
                    if carry is not None:
                        self._materialize(carry)
                        carry = None
                    self._apply_pending_swap()
                if self._admit(ring):
                    if carry is not None:
                        self._materialize(carry)
                        carry = None
                if self._stop.is_set():
                    break
                if not ring:
                    continue
                try:
                    carry = self._ring_step(ring, carry)
                except BaseException as exc:  # fail the ring, keep serving
                    for slot in ring:
                        slot.req.ticket._fail(
                            ServeError(f"ring step failed: {exc!r}"))
                        self._respond_span(slot.req, "failed",
                                           steps_done=slot.steps_done)
                        if slot.is_traj:
                            self._traj_exit()
                    ring.clear()
                    carry = None
            self._drained_ev.set()
        finally:
            # Stop: the remaining rows were ASKED to die — retryable
            # backpressure. A crash unwinding through here instead means
            # their device state is lost mid-flight: also retryable (the
            # supervisor restarts the worker, but ring rows cannot be
            # replayed — their PRNG position is gone), with a hint.
            if self._stop.is_set():
                err_msg, after = "service stopped", 1.0
            else:
                err_msg = ("serving worker died mid-flight; in-ring "
                           "state lost — safe to retry")
                after = self.serve.worker_backoff_s * 2
            for slot in ring:
                slot.req.ticket._fail(Rejected(
                    err_msg, retryable=True, retry_after_s=after))
                self._respond_span(slot.req, "failed",
                                   steps_done=slot.steps_done)
                if slot.is_traj:
                    self._traj_exit()
            self._ring_debt = 0

    def _admit(self, ring: List[_Slot]) -> bool:
        """Move queued requests into free ring slots; True if the ring
        composition changed. Blocks only while the ring is empty and
        there is nothing to do. On an EMPTY ring the oldest request is
        held open for flush_timeout_ms so co-riders share the first
        dispatch (the whole-request dispatcher's coalescing contract);
        with steps already in flight arrivals join immediately. While a
        swap is pending nothing is admitted — the ring drains, queued
        requests ride the new version."""
        flush_s = self.serve.flush_timeout_ms / 1000.0
        admitted: List[_Request] = []
        expired: List[tuple] = []
        with self._queue_cv:
            if not ring:
                while (not self._queue and not self._stop.is_set()
                       and self._pending_swap is None
                       and not self._draining):
                    self._queue_cv.wait(timeout=0.1)
                if (self._stop.is_set() or not self._queue
                        or self._pending_swap is not None):
                    return False
                head = self._queue[0]
                deadline = head.t_submit + flush_s
                shape = head.shape
                while not self._stop.is_set():
                    ready = sum(1 for r in self._queue if r.shape == shape)
                    if ready >= self.serve.max_batch:
                        break
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._queue_cv.wait(timeout=min(remaining, 0.05))
                if self._stop.is_set():
                    return False
            elif self._pending_swap is not None:
                return False
            shape = ring[0].shape if ring else None
            kept: List[_Request] = []
            now = time.monotonic()
            free = self.serve.max_batch - len(ring)
            for r in self._queue:
                waited = now - r.t_submit
                if r.deadline_s and waited > r.deadline_s:
                    expired.append((r, waited))
                    continue
                if shape is None:
                    shape = r.shape
                if r.shape == shape and len(admitted) < free:
                    admitted.append(r)
                else:
                    kept.append(r)  # full ring or foreign image size
            self._queue.clear()
            self._queue.extend(kept)
        for r, waited in expired:
            self._log_event(
                r.ticket.request_id, "deadline",
                f"queued {waited * 1e3:.1f}ms > deadline "
                f"{r.deadline_s * 1e3:.0f}ms")
            msg = (f"request waited {waited * 1e3:.1f}ms, deadline was "
                   f"{r.deadline_s * 1e3:.0f}ms")
            r.ticket._fail(
                TrajectoryExpired(msg, frames=[], frame_index=0)
                if r.is_traj else DeadlineExceeded(msg))
            self._respond_span(r, "expired")
        if not admitted:
            return False
        now = time.monotonic()
        version = self._live[1]
        for r in admitted:
            # Swap-drain attribution: every swap applied between this
            # request's submission and its ring admission drained the
            # ring in its path (the drain-on-swap contract).
            r.swap_drains = self._swaps - r.swaps_at_submit
            steps = int(r.program_key[2])
            try:
                bank = self._banks.get(steps)
                fbank = None
                if r.is_traj:
                    # One conditioning upload per ORBIT (here), not per
                    # frame: the bank seeds with the source view and
                    # grows on device as frames commit in-jit.
                    fbank = FrameBank(self._k_max, r.k_cap, r.cond["x"],
                                      r.cond["R1"], r.cond["t1"])
                cc = cc_bank = None
                if self._cond_cache:
                    # The cond-cache tentpole: encode the request's
                    # conditioning branch ONCE, here, at admission; the
                    # step program consumes the activations as device
                    # arguments every step of the row's lifetime.
                    cc, cc_bank = self._admit_encode(r, fbank)
            except Exception as exc:
                # A request the schedule/bank math cannot serve (e.g. a
                # step count respace() rejects) fails ITS ticket — an
                # admission error must never kill the worker thread and
                # wedge every later request behind it.
                r.ticket._fail(Rejected(
                    f"admission failed for request "
                    f"{r.ticket.request_id}: {exc!r}"))
                self._respond_span(r, "failed")
                continue
            if r.is_traj:
                self._traj_in_ring += 1
                self._traj_active.set(float(self._traj_in_ring))
            slot = _Slot(r, bank, version, now, fbank=fbank)
            slot.cc, slot.cc_bank = cc, cc_bank
            ring.append(slot)
            # step_wait: submit → ring admission (the stepper's analogue
            # of queue_wait; bounded by steps in flight, not by whole
            # requests ahead).
            self.tracer.add_span("step_wait", now - r.t_submit,
                                 request_id=r.ticket.request_id,
                                 steps=slot.bank.n,
                                 trace_id=r.trace_id,
                                 parent_id=reqtrace.root_span_id(
                                     r.trace_id),
                                 swap_drains=r.swap_drains)
        return True

    def _place(self, tree, bucket: int):
        """Device placement for one ring dispatch: shard over the mesh
        'data' axis when the bucket divides it, replicate over the mesh
        otherwise, default device without a mesh (same policy as the
        whole-request dispatcher)."""
        if mesh_lib.divides_data_axis(self.mesh, bucket):
            return mesh_lib.shard_batch(self.mesh, tree)
        if self.mesh is not None:
            return jax.device_put(tree, mesh_lib.replicated(self.mesh))
        return jax.device_put(tree, jax.devices()[0])

    def _false_rows(self, bucket: int):
        """Cached device-staged all-False (bucket,) `first` vector."""
        dev = self._false_cache.get(bucket)
        if dev is None:
            dev = self._place(np.zeros(bucket, bool), bucket)
            self._false_cache[bucket] = dev
        return dev

    def _materialize(self, carry: dict) -> None:
        """Pull the carry's device-resident (z, keys) back into the host
        slot state — the ring composition is about to change, so the next
        dispatch rebuilds its batch from rows."""
        z_host = np.asarray(jax.device_get(carry["z"]))
        k_host = np.asarray(jax.device_get(carry["keys"]))
        for i, slot in enumerate(carry["slots"]):
            slot.z = z_host[i]
            slot.keys = k_host[i]

    def _step_cache_key(self, bucket: int, H: int, W: int) -> tuple:
        """Stepper program identity: bucket SHAPE plus the DiffusionConfig
        fields the compiled step bakes in — including the serving
        precision and the fused-step flag, which change the lowered
        program (in-jit dequant / the Pallas kernel call). Deliberately
        NO steps, t, or guidance weight — those are device arguments,
        which is what makes a mixed 4/256-step warm sweep compile
        nothing (the PR 3 key folded `steps` in, which under step-level
        scheduling would have recompiled per step count). k_max and
        stochastic_cond ride along but are SERVICE constants (they size
        the bank arrays / pick the gather), so mixed single-shot and
        trajectory traffic still shares one program per bucket."""
        d = self.diffusion
        return (bucket, H, W, d.sampler, d.cfg_rescale, d.ddim_eta,
                d.objective, d.clip_denoised, d.schedule, d.timesteps,
                self.precision, d.fused_step, self._k_max,
                d.stochastic_cond, self._cond_cache)

    def _build_step_program(self):
        return make_ring_step_fn(
            self.model, self.diffusion, k_max=self._k_max,
            cond_cache=self._cond_cache,
            param_transform=self._param_transform)

    def _zero_bank(self, H: int, W: int) -> tuple:
        """Staged-once zero bank arrays for single-shot rows riding a
        bank-enabled ring (their count=0 row never reads them)."""
        import jax.numpy as jnp

        zb = self._zero_bank_cache.get((H, W))
        if zb is None:
            zb = (jnp.zeros((self._k_max, H, W, 3), jnp.float32),
                  jnp.zeros((self._k_max, 3, 3), jnp.float32),
                  jnp.zeros((self._k_max, 3), jnp.float32))
            self._zero_bank_cache[(H, W)] = zb
        return zb

    # -- conditioning cache (serve.cond_cache) --------------------------
    @staticmethod
    def _cc_nbytes(cc) -> int:
        """Device bytes of one cached-conditioning pytree."""
        if cc is None:
            return 0
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(cc))

    def _encode_call(self, cond: dict, mask: np.ndarray) -> tuple:
        """Run the admission-time encode program and account it: one
        miss counter tick per call, and a compile-ledger entry whenever
        the call grew the encode jit cache (a NEW (B, H, W) encode shape
        — the event the warm-traffic zero-recompile asserts police,
        under the name 'serve_cond_encode')."""
        params, _ = self._live
        t0 = time.perf_counter()
        pose, feats = self._encode_fn(params, cond, mask)
        jax.block_until_ready(feats)
        wall = time.perf_counter() - t0
        self._cc_misses += 1
        self._cond_misses_total.inc()
        size_fn = getattr(self._encode_fn, "_cache_size", None)
        size = int(size_fn()) if callable(size_fn) else 0
        if size > self._encode_entries:
            self._encode_entries = size
            x = np.asarray(cond["x"])
            self._compile_ledger.record(
                "serve_cond_encode",
                {"args": {"B": repr(int(x.shape[0])),
                          "H": repr(int(x.shape[1])),
                          "W": repr(int(x.shape[2]))}},
                wall_s=wall, backend=jax.default_backend())
        return tuple(pose), feats

    def _ensure_uncond(self, H: int, W: int, cond1: dict) -> bool:
        """Fill the global per-(H, W) uncond pose-embedding cache if
        empty; True on a hit. The CFG mask zeroes the pose embedding
        before the per-level convs, so the masked halves are request-
        independent (but NOT zero — conv biases and learned embeddings
        survive): any request's conditioning serves, at B=1, and the
        (1, …) result broadcasts in-program over every guidance pair."""
        key = (H, W)
        if key in self._uncond_cache:
            return True
        pose, _ = self._encode_call(
            cond1, np.zeros((cond1["x"].shape[0],), np.float32))
        if self.mesh is not None:
            pose = jax.device_put(pose, mesh_lib.replicated(self.mesh))
        self._uncond_cache[key] = pose
        return False

    def _encode_bank(self, fbank: FrameBank, R2, t2, K) -> tuple:
        """Encode every bank entry against the CURRENT target pose, at
        B=k_max (zero-padded entries encode garbage that idx, bounded by
        count, never selects). Called at trajectory admission and again
        at each frame boundary — exactly when the target pose advances
        and the bank grows."""
        k = fbank.k_max
        cond = {
            "x": fbank.x, "R1": fbank.R, "t1": fbank.t,
            "R2": np.broadcast_to(
                np.asarray(R2, np.float32), (k, 3, 3)),
            "t2": np.broadcast_to(np.asarray(t2, np.float32), (k, 3)),
            "K": np.broadcast_to(np.asarray(K, np.float32), (k, 3, 3)),
        }
        return self._encode_call(cond, np.ones((k,), np.float32))

    def _admit_encode(self, r: _Request,
                      fbank: Optional[FrameBank]) -> tuple:
        """The admission-time encode (the cond-cache tentpole): one
        B=1 encode for the request's cond branch, the shared uncond
        fill if this (H, W) has none yet, and — for trajectories — the
        B=k_max bank-entry encode against the first target pose.
        Returns (cc, cc_bank) for the slot. Runs inside _admit's
        per-request try: an encode failure fails THIS ticket, never the
        worker."""
        H, W = r.shape
        cond1 = {k: np.asarray(r.cond[k])[None] for k in COND_KEYS}
        with self.tracer.span(
                "cond_cache",
                request_id=r.ticket.request_id,
                trace_id=r.trace_id,
                parent_id=reqtrace.root_span_id(r.trace_id)) as span:
            uncond_hit = self._ensure_uncond(H, W, cond1)
            cc = self._encode_call(cond1, np.ones((1,), np.float32))
            cc_bank = None
            if fbank is not None:
                cc_bank = self._encode_bank(
                    fbank, r.poses_R[0], r.poses_t[0], r.cond["K"])
            span.set(uncond=("hit" if uncond_hit else "miss"),
                     bytes=self._cc_nbytes(cc) + self._cc_nbytes(cc_bank))
        return cc, cc_bank

    def _zero_cc_bank(self, H: int, W: int, cc: tuple) -> tuple:
        """Staged-once zero cached-bank activations for single-shot rows
        riding a cond-cached bank ring (count=0 rows never select them);
        shapes derived from a request-level cc, which the admission
        order guarantees exists before any stack needs zeros."""
        import jax.numpy as jnp

        zb = self._zero_cc_cache.get((H, W))
        if zb is None:
            pose_c, feats_c = cc
            zb = (tuple(
                jnp.zeros((self._k_max,) + p.shape[1:], p.dtype)
                for p in pose_c),
                jnp.zeros((self._k_max,) + feats_c.shape[1:],
                          feats_c.dtype))
            self._zero_cc_cache[(H, W)] = zb
        return zb

    def _cc_resident(self, ring: List[_Slot]) -> int:
        """Current device residency of the conditioning cache: every
        ring slot's activations plus the shared uncond halves."""
        total = sum(self._cc_nbytes(s.cc) + self._cc_nbytes(s.cc_bank)
                    for s in ring)
        total += sum(self._cc_nbytes(p)
                     for p in self._uncond_cache.values())
        return total

    def _bank_sig(self, ring: List[_Slot]) -> tuple:
        """Identity of the ring's stacked bank content: any commit bumps
        a slot's total, forcing a device-side restack next dispatch."""
        return tuple((id(s), s.fbank.total) if s.is_traj else None
                     for s in ring)

    def _stack_banks(self, ring: List[_Slot], bucket: int,
                     H: int, W: int) -> tuple:
        """Stack per-slot bank arrays into the (bucket, k_max, …) step
        arguments — a DEVICE-side stack (the per-slot banks are already
        device-resident), placed like every other ring tensor."""
        import jax.numpy as jnp

        zx, zR, zt = self._zero_bank(H, W)
        pad = bucket - len(ring)
        xs = [s.fbank.x if s.is_traj else zx for s in ring] + [zx] * pad
        Rs = [s.fbank.R if s.is_traj else zR for s in ring] + [zR] * pad
        ts = [s.fbank.t if s.is_traj else zt for s in ring] + [zt] * pad
        return (self._place(jnp.stack(xs), bucket),
                self._place(jnp.stack(Rs), bucket),
                self._place(jnp.stack(ts), bucket))

    def _traj_exit(self) -> None:
        self._traj_in_ring = max(0, self._traj_in_ring - 1)
        self._traj_active.set(float(self._traj_in_ring))

    def _ring_step(self, ring: List[_Slot],
                   carry: Optional[dict]) -> Optional[dict]:
        """One denoise step over the whole ring. Returns the device-
        resident carry for the next iteration, or None when rows exited
        (the composition changed, so the next dispatch rebuilds).

        Trajectory frame boundaries are NOT composition changes: a slot
        whose frame finished streams it to the client, commits it into
        its device bank in-jit, and re-arms for the next pose while the
        carry (z, keys, cond, banks) stays on device — only an expiry or
        the orbit's LAST frame makes the slot exit the ring."""
        self.dispatches += 1
        self._last_dispatch_t = time.time()
        if self._profiler is not None:
            self._profiler.on_step(self.dispatches)
        faultinject.maybe_serve_dispatch_raise(self.dispatches)
        faultinject.maybe_serve_slow_step(self.dispatches)
        nan_at = faultinject.serve_nan_spec()
        if nan_at is not None and nan_at[0] == self.dispatches:
            # Poison one row's carried latent at the host boundary; the
            # DEVICE-side finite mask must catch it downstream — the
            # drill proves detection, not just injection.
            if carry is not None:
                self._materialize(carry)
                carry = None
            victim = ring[min(nan_at[1], len(ring) - 1)]
            if victim.z is not None:
                victim.z = np.full_like(victim.z, np.nan)
        n = len(ring)
        bucket = bucket_for(n, self.serve.max_batch)
        H, W = ring[0].shape
        params, _ = self._live
        pad = bucket - n
        sig = (tuple(id(s) for s in ring), bucket)
        bank_mode = self._k_max > 0
        bank_dev = bank_sig = None
        cc_pose = cc_feats = cc_uncond = cc_bank_dev = None
        with self.tracer.span("batch_form", bucket=bucket, batch_n=n):
            if carry is not None and carry["sig"] != sig:
                self._materialize(carry)
                carry = None
            if carry is None:
                zeros_img = np.zeros((H, W, 3), np.float32)
                z = np.stack(
                    [s.z if s.z is not None else zeros_img for s in ring]
                    + [zeros_img] * pad)
                keys = np.stack([s.keys for s in ring]
                                + [np.zeros(2, np.uint32)] * pad)
                cond = {
                    k: np.stack([s.req.cond[k] for s in ring]
                                + [ring[-1].req.cond[k]] * pad)
                    for k in COND_KEYS
                }
                z_dev = self._place(z, bucket)
                keys_dev = self._place(keys, bucket)
                cond_dev = self._place(cond, bucket)
            else:
                z_dev, keys_dev, cond_dev = (
                    carry["z"], carry["keys"], carry["cond"])
            if self._cond_cache:
                # Slot-level cached activations: a DEVICE-side
                # concatenate of the per-slot B=1 encodes (pad rows
                # repeat the last real row, like cond) — restacked only
                # when the ring composition changes, exactly the cond
                # lifecycle. The shared uncond halves ride as (1, …)
                # device arguments broadcast in-program.
                import jax.numpy as jnp
                if carry is None:
                    rows = [s.cc for s in ring] + [ring[-1].cc] * pad
                    cc_pose = tuple(
                        self._place(jnp.concatenate(
                            [r[0][lev] for r in rows], axis=0), bucket)
                        for lev in range(len(rows[0][0])))
                    cc_feats = self._place(jnp.concatenate(
                        [r[1] for r in rows], axis=0), bucket)
                else:
                    cc_pose, cc_feats = carry["cc"]
                cc_uncond = self._uncond_cache[(H, W)]
            # Per-row schedule coefficients: ONE packed (B, K) host
            # gather + device transfer per step (bank.table rows) — this
            # is what keeps t/steps/w out of the program identity. Pad
            # rows repeat the last real row's coefficients so their
            # (discarded) math stays finite. `first`/`w` only change
            # when the ring composition does, so the carry fast path
            # re-uploads nothing but the coefficient matrix (plus, in
            # bank mode, the tiny per-step pose/fill vectors).
            last = ring[-1]
            coefs = np.stack(
                [s.bank.table[s.t] for s in ring]
                + [last.bank.table[last.t]] * pad)
            coefs_dev = self._place(coefs, bucket)
            if carry is None:
                first = np.asarray([s.first for s in ring] + [False] * pad)
                w = np.asarray([s.w for s in ring] + [last.w] * pad,
                               np.float32)
                first_dev = self._place(first, bucket)
                w_dev = self._place(w, bucket)
            else:
                w_dev = carry["w"]
                if any(s.first for s in ring):
                    # Trajectory re-arms flipped `first` back on mid-
                    # carry: one (bucket,) bool upload re-draws ONLY
                    # those rows' init noise.
                    first_dev = self._place(
                        np.asarray([s.first for s in ring]
                                   + [False] * pad), bucket)
                else:
                    first_dev = carry["first"]
            if bank_mode:
                # The current frame's target pose and the bank fill ride
                # as DEVICE ARGUMENTS (like the coefficients), so
                # advancing a trajectory to its next orbit pose never
                # rebuilds the ring or touches the program identity —
                # but they only CHANGE at frame boundaries, so the carry
                # fast path reuses the staged vectors between them.
                bank_sig = self._bank_sig(ring)
                if carry is not None and carry.get("bank_sig") == bank_sig:
                    R2_dev, t2_dev, state_dev = carry["pose"]
                    bank_dev = carry["bank"]
                    if self._cond_cache:
                        cc_bank_dev = carry["cc_bank"]
                else:
                    tp = [s.target_pose() for s in ring]
                    R2s = np.stack([p[0] for p in tp] + [tp[-1][0]] * pad
                                   ).astype(np.float32)
                    t2s = np.stack([p[1] for p in tp] + [tp[-1][1]] * pad
                                   ).astype(np.float32)
                    state = np.asarray(
                        [[s.fbank.count, s.fbank.latest] if s.is_traj
                         else [0, 0] for s in ring] + [[0, 0]] * pad,
                        np.int32)
                    R2_dev = self._place(R2s, bucket)
                    t2_dev = self._place(t2s, bucket)
                    state_dev = self._place(state, bucket)
                    bank_dev = self._stack_banks(ring, bucket, H, W)
                    if self._cond_cache:
                        # Cached bank-entry activations follow the bank
                        # lifecycle: restacked when a commit (or a frame
                        # boundary's re-encode) bumps the bank_sig.
                        import jax.numpy as jnp
                        cbs = [s.cc_bank if s.is_traj
                               else self._zero_cc_bank(H, W, s.cc)
                               for s in ring]
                        cbs += [cbs[-1]] * pad
                        cc_bank_dev = (
                            tuple(self._place(jnp.stack(
                                [c[0][lev] for c in cbs]), bucket)
                                for lev in range(len(cbs[0][0]))),
                            self._place(jnp.stack(
                                [c[1] for c in cbs]), bucket))
            entry = self._programs.get(self._step_cache_key(bucket, H, W))
        cold = not entry["warm"]
        t0 = time.perf_counter()
        if bank_mode:
            args = (params, z_dev, keys_dev, first_dev, cond_dev,
                    coefs_dev, w_dev, R2_dev, t2_dev, bank_dev[0],
                    bank_dev[1], bank_dev[2], state_dev)
            if self._cond_cache:
                args += ((cc_pose, cc_uncond, cc_feats,
                          cc_bank_dev[0], cc_bank_dev[1]),)
            z_next, keys_next, finite_dev = entry["fn"](*args)
        else:
            args = (params, z_dev, keys_dev, first_dev, cond_dev,
                    coefs_dev, w_dev)
            if self._cond_cache:
                args += ((cc_pose, cc_uncond, cc_feats),)
            z_next, keys_next, finite_dev = entry["fn"](*args)
        jax.block_until_ready(z_next)
        self._pace_dispatch(t0)
        elapsed = time.perf_counter() - t0
        entry["warm"] = True
        # Rider attribution (obs/reqtrace.py contract): ONE row per
        # dispatch naming every rider, the service-global dispatch
        # ordinal, and the step debt ENTERING this dispatch — per-request
        # timelines are joined offline, so tracing cost doesn't scale
        # with batch size.
        debt_in = sum(
            (s.t + 1) + ((s.req.num_frames - s.frame_index - 1)
                         * s.bank.n if s.is_traj else 0)
            for s in ring)
        for s in ring:
            s.req.rides += 1
        step_attrs = dict(bucket=bucket, batch_n=n,
                          dispatch=self.dispatches,
                          riders=",".join(
                              str(s.req.ticket.request_id)
                              for s in ring),
                          debt=debt_in)
        if self._cond_cache:
            # Cache-hit attribution: every row this dispatch stepped was
            # served from cached activations (the cache is filled at
            # admission, before the row's first step, so there is no
            # partially-cached row).
            resident = self._cc_resident(ring)
            self._cc_hits += n
            self._cond_hits_total.inc(n)
            self._cond_resident_gauge.set(float(resident))
            step_attrs.update(cc_hits=n, cc_bytes=resident)
        self.tracer.add_span("compile" if cold else "ring_step", elapsed,
                             **step_attrs)
        self.stats.record_span("ring_step", elapsed)
        # In-ring anomaly quarantine: the step program's third output is
        # a per-row finite mask (a device-side reduce — the host reads a
        # (bucket,) bool, never the latent). A row under strikes keeps
        # stepping (NaN can't heal, but the ladder is explicit); a row
        # AT the strike budget — or any non-finite row at a frame or
        # request boundary, where the only alternative is emitting the
        # garbage — is evicted and its ticket failed with SampleAnomaly.
        finite = np.asarray(jax.device_get(finite_dev))
        anomalous: List[_Slot] = []
        for i, s in enumerate(ring):
            if finite[i]:
                s.strikes = 0
            else:
                s.strikes += 1
                if s.strikes >= self.serve.anomaly_strikes:
                    anomalous.append(s)
        anom_ids = {id(s) for s in anomalous}
        finished: List[_Slot] = []
        rearm: List[_Slot] = []
        for i, s in enumerate(ring):
            if s.first:
                s.bucket0, s.batch0 = bucket, n
                s.first = False
            # Cold dispatches land in compile_s, warm ones in device_s —
            # the 'device' span keeps its PR 3 meaning (warm device time).
            if cold:
                s.compile_s += elapsed
            else:
                s.device_s += elapsed
            s.steps_done += 1
            s.t -= 1
            if id(s) in anom_ids:
                continue
            if s.t < 0:
                if not finite[i]:
                    # Boundary forces the verdict regardless of strike
                    # budget: a non-finite frame must never stream,
                    # resolve, or commit into a bank.
                    anomalous.append(s)
                    anom_ids.add(id(s))
                elif s.is_traj and s.frame_index + 1 < s.req.num_frames:
                    rearm.append(s)
                else:
                    finished.append(s)
        self._ring_debt = sum(
            (s.t + 1) + ((s.req.num_frames - s.frame_index - 1)
                         * s.bank.n if s.is_traj else 0)
            for s in ring if id(s) not in anom_ids)
        if not finished and not rearm and not anomalous:
            # Every continuing row has now taken its first step, so the
            # carried `first` is the cached all-False vector (reusing
            # this dispatch's `first_dev` would re-draw init noise).
            return {"z": z_next, "keys": keys_next, "cond": cond_dev,
                    "first": self._false_rows(bucket), "w": w_dev,
                    "sig": sig, "slots": list(ring),
                    "bank": bank_dev, "bank_sig": bank_sig,
                    "pose": ((R2_dev, t2_dev, state_dev) if bank_mode
                             else None),
                    "cc": (cc_pose, cc_feats), "cc_bank": cc_bank_dev}
        fin_ids = {id(s) for s in finished}
        rearm_ids = {id(s) for s in rearm}
        z_host = k_host = None
        if finished:
            z_host = np.asarray(jax.device_get(z_next))
            k_host = np.asarray(jax.device_get(keys_next))
        expired: List[_Slot] = []
        with self.tracer.span("respond",
                              batch_n=(len(finished) + len(rearm)
                                       + len(anomalous))):
            for s in anomalous:
                self._quarantine_slot(s)
            for i, s in enumerate(ring):
                if id(s) in rearm_ids:
                    # Frame boundary: deliver + in-jit bank commit +
                    # re-arm (or expire at this frame's admission).
                    frame_dev = z_next[i]
                    frame = (z_host[i] if z_host is not None
                             else np.asarray(jax.device_get(frame_dev)))
                    if not self._frame_boundary(s, frame, frame_dev):
                        expired.append(s)
                elif id(s) in fin_ids:
                    if s.is_traj:
                        self._finish_trajectory(s, z_host[i])
                    else:
                        self._resolve_slot(s, z_host[i])
            if not finished and not expired and not anomalous:
                # Pure frame boundary: the ring composition is
                # unchanged, the carry stays device-resident. The stale
                # bank_sig forces a device-side restack next dispatch
                # (the re-armed slots' banks just grew — and, under the
                # cond cache, their cc_bank was just re-encoded).
                return {"z": z_next, "keys": keys_next, "cond": cond_dev,
                        "first": self._false_rows(bucket), "w": w_dev,
                        "sig": sig, "slots": list(ring),
                        "bank": bank_dev, "bank_sig": bank_sig,
                        "pose": (R2_dev, t2_dev, state_dev),
                        "cc": (cc_pose, cc_feats), "cc_bank": cc_bank_dev}
            # Rows exited: rebuild next dispatch from host state.
            if z_host is None:
                z_host = np.asarray(jax.device_get(z_next))
                k_host = np.asarray(jax.device_get(keys_next))
            exit_ids = fin_ids | {id(s) for s in expired} | anom_ids
            keep: List[_Slot] = []
            for i, s in enumerate(ring):
                if id(s) in exit_ids:
                    continue
                s.z = z_host[i]
                s.keys = k_host[i]
                keep.append(s)
            ring[:] = keep
        return None

    def _quarantine_slot(self, slot: _Slot) -> None:
        """Evict a poisoned ring row: fail its ticket with a structured
        SampleAnomaly, log + count the anomaly, and never let the
        non-finite latent reach a stream, a resolution, or a bank
        commit. Co-riders are untouched (ring-composition invariance
        bounds the blast radius to one row)."""
        req = slot.req
        self.anomalies += 1
        self._anomalies_total.inc()
        where = f"after step {slot.steps_done}"
        if slot.is_traj:
            where += (f" of frame {slot.frame_index}/"
                      f"{req.num_frames}")
        self._log_event(
            req.ticket.request_id, "anomaly",
            f"non-finite latent {where} (strike {slot.strikes}/"
            f"{self.serve.anomaly_strikes}); slot quarantined, ticket "
            "failed retryably")
        msg = (f"sample went non-finite {where}; the row was "
               "quarantined before anything was streamed or committed "
               "— safe to retry")
        if slot.is_traj:
            with req.ticket._lock:
                done_frames = list(req.ticket._frames)
            req.ticket._fail(SampleAnomaly(
                msg + f"; {len(done_frames)} completed frames attached",
                frames=done_frames, frame_index=slot.frame_index))
            self._traj_exit()
        else:
            req.ticket._fail(SampleAnomaly(msg))
        self._respond_span(
            req, "anomaly", steps_done=slot.steps_done,
            frames_done=slot.frame_index if slot.is_traj else None)
        self.flight.dump("anomaly", request_id=req.ticket.request_id,
                         dispatch=self.dispatches,
                         steps_done=slot.steps_done)

    def _frame_boundary(self, slot: _Slot, frame: np.ndarray,
                        frame_dev) -> bool:
        """One finished (non-final) trajectory frame: stream it, commit
        it into the slot's device bank in-jit, check the request
        deadline AT THIS FRAME'S ADMISSION, and re-arm the slot for the
        next pose. Returns False when the deadline expired (the slot
        must leave the ring; completed frames ride the error)."""
        req = slot.req
        now = time.monotonic()
        self._stream_frame(slot, frame, now)
        R2, t2 = slot.target_pose()
        slot.fbank.commit(self._commit_fn, frame_dev, R2, t2)
        slot.frame_index += 1
        waited = now - req.t_submit
        if req.deadline_s and waited > req.deadline_s:
            self._log_event(
                req.ticket.request_id, "deadline",
                f"trajectory expired at frame {slot.frame_index}/"
                f"{req.num_frames} admission: {waited * 1e3:.1f}ms > "
                f"deadline {req.deadline_s * 1e3:.0f}ms")
            with req.ticket._lock:
                done_frames = list(req.ticket._frames)
            req.ticket._fail(TrajectoryExpired(
                f"trajectory deadline ({req.deadline_s * 1e3:.0f}ms) "
                f"passed after {slot.frame_index} of {req.num_frames} "
                f"frames ({waited * 1e3:.1f}ms elapsed); completed "
                "frames attached",
                frames=done_frames, frame_index=slot.frame_index))
            self._respond_span(req, "expired",
                               steps_done=slot.steps_done,
                               frames_done=slot.frame_index)
            self._traj_exit()
            return False
        if self._cond_cache:
            # Re-encode the bank-entry activations for the NEXT frame:
            # its target pose changes every entry's pose embedding, and
            # the bank just grew by the committed frame. frame_index was
            # advanced above, so target_pose() is the next pose — the
            # same one the next dispatch restacks into R2/t2 (the stale
            # bank_sig forces that restack, which also picks this up).
            # Runs on the pinned weights: swaps drain the ring, so
            # self._live cannot change while this slot is in flight.
            R2n, t2n = slot.target_pose()
            slot.cc_bank = self._encode_bank(slot.fbank, R2n, t2n,
                                             req.cond["K"])
        slot.t = slot.bank.n - 1
        slot.first = True  # next frame draws fresh init noise in-jit
        slot.frame_t0 = now
        return True

    def _stream_frame(self, slot: _Slot, frame: np.ndarray,
                      now: float) -> None:
        """Deliver one completed frame on the trajectory ticket and
        account it (span + gauges + per-frame telemetry row)."""
        req = slot.req
        dur = max(0.0, now - slot.frame_t0)
        timing = {"frame_index": slot.frame_index, "frame_s": dur,
                  "steps": slot.bank.n, "model_version": slot.version}
        req.ticket.model_version = slot.version
        req.ticket._deliver(frame, timing)
        # Per-frame telemetry: a `trajectory_frame` span row (child of
        # the ring_step stream) lands in telemetry.jsonl with the
        # request id + frame index via the bus-wired tracer.
        self.tracer.add_span("trajectory_frame", dur,
                             request_id=req.ticket.request_id,
                             frame_index=slot.frame_index,
                             steps=slot.bank.n,
                             model_version=slot.version,
                             trace_id=req.trace_id,
                             parent_id=reqtrace.root_span_id(
                                 req.trace_id))
        self.stats.record_span("trajectory_frame", dur)
        self._frames_count += 1
        self._frames_total.inc()
        if self._frames_t0 is None:
            self._frames_t0 = time.perf_counter()
        elapsed = time.perf_counter() - self._frames_t0
        if elapsed > 0:
            self._frames_per_sec.set(self._frames_count / elapsed)

    def _finish_trajectory(self, slot: _Slot, frame: np.ndarray) -> None:
        """The orbit's LAST frame: deliver it and complete the ticket."""
        req = slot.req
        now = time.monotonic()
        self._stream_frame(slot, frame, now)
        qw = max(0.0, slot.t_admit - req.t_submit)
        timing = {
            "queue_wait_s": qw,
            "device_s": slot.device_s,
            "bucket": slot.bucket0,
            "batch_n": slot.batch0,
            "steps": slot.steps_done,
            "frames": req.num_frames,
            "model_version": slot.version,
        }
        if slot.compile_s:
            timing["compile_s"] = slot.compile_s
        req.ticket.model_version = slot.version
        self.stats.record_span("queue_wait", qw)
        self.stats.record_span("device", slot.device_s)
        if slot.compile_s:
            self.stats.record_span("compile", slot.compile_s)
        self.tracer.add_span("queue_wait", qw,
                             request_id=req.ticket.request_id,
                             trace_id=req.trace_id,
                             parent_id=reqtrace.root_span_id(
                                 req.trace_id))
        req.ticket._complete(timing)
        self._respond_span(req, "ok", steps_done=slot.steps_done,
                           frames_done=req.num_frames)
        self.stats.count_requests(1)
        self._requests_total.inc(1)
        self._traj_exit()

    def _resolve_slot(self, slot: _Slot, image: np.ndarray) -> None:
        req = slot.req
        qw = max(0.0, slot.t_admit - req.t_submit)
        timing = {
            "queue_wait_s": qw,
            "device_s": slot.device_s,
            "bucket": slot.bucket0,
            "batch_n": slot.batch0,
            "steps": slot.steps_done,
            "model_version": slot.version,
        }
        if slot.compile_s:
            timing["compile_s"] = slot.compile_s
        req.ticket.model_version = slot.version
        self.stats.record_span("queue_wait", qw)
        self.stats.record_span("device", slot.device_s)
        if slot.compile_s:
            self.stats.record_span("compile", slot.compile_s)
        self.tracer.add_span("queue_wait", qw,
                             request_id=req.ticket.request_id,
                             trace_id=req.trace_id,
                             parent_id=reqtrace.root_span_id(
                                 req.trace_id))
        req.ticket._resolve(image, timing)
        self._respond_span(req, "ok", steps_done=slot.steps_done)
        self.stats.count_requests(1)
        self._requests_total.inc(1)

    def _collect_group(self) -> List[_Request]:
        """Pop one coalescable group: same program key, oldest first,
        held open for flush_timeout_ms or until max_batch riders."""
        flush_s = self.serve.flush_timeout_ms / 1000.0
        with self._queue_cv:
            while (not self._queue and not self._stop.is_set()
                   and self._pending_swap is None
                   and not self._draining):
                self._queue_cv.wait(timeout=0.1)
            if self._stop.is_set():
                return []
            if not self._queue:
                return []  # woken by a swap/drain: let _run handle it
            first = self._queue[0]
            key = first.program_key
            deadline = first.t_submit + flush_s
            while True:
                ready = sum(1 for r in self._queue if r.program_key == key)
                if ready >= self.serve.max_batch or self._stop.is_set():
                    break
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._queue_cv.wait(timeout=min(remaining, 0.05))
            if self._stop.is_set():
                return []  # stop() fails whatever is still queued
            group: List[_Request] = []
            kept: List[_Request] = []
            for r in self._queue:
                if (r.program_key == key
                        and len(group) < self.serve.max_batch):
                    group.append(r)
                else:
                    kept.append(r)
            self._queue.clear()
            self._queue.extend(kept)
        # Expire requests whose queue wait blew their deadline — serving
        # them would spend device time on an answer nobody is waiting for.
        now = time.monotonic()
        live = []
        for r in group:
            waited = now - r.t_submit
            if r.deadline_s and waited > r.deadline_s:
                self._log_event(
                    r.ticket.request_id, "deadline",
                    f"queued {waited * 1e3:.1f}ms > deadline "
                    f"{r.deadline_s * 1e3:.0f}ms")
                r.ticket._fail(DeadlineExceeded(
                    f"request waited {waited * 1e3:.1f}ms, deadline was "
                    f"{r.deadline_s * 1e3:.0f}ms"))
                self._respond_span(r, "expired")
            else:
                live.append(r)
        return live

    # Field names matching the program-cache key tuples positionally —
    # the ledger fingerprints each key field by name so a recompile diff
    # reads "steps: 4 -> 256", not "position 3 changed".
    _STEP_KEY_FIELDS = ("bucket", "H", "W", "sampler", "cfg_rescale",
                        "ddim_eta", "objective", "clip_denoised",
                        "schedule", "timesteps", "precision", "fused_step",
                        "k_max", "stochastic_cond", "cond_cache")
    _BATCH_KEY_FIELDS = ("bucket", "H", "W", "steps", "guidance",
                         "sampler", "cfg_rescale", "ddim_eta", "objective",
                         "schedule", "precision", "fused_step")

    def _record_build(self, key: tuple, build_s: float) -> None:
        """Program-cache build observer → compile ledger entry. The
        ledger keys every sampler build under ONE name so any second
        build is classified (and diffed) as a recompile — exactly the
        event the warm-sweep zero-recompile asserts police."""
        fields = (self._STEP_KEY_FIELDS
                  if self.serve.scheduler == "step"
                  else self._BATCH_KEY_FIELDS)
        args = {name: repr(v) for name, v in zip(fields, key)}
        self._compile_ledger.record(
            f"serve_{self.serve.scheduler}", {"args": args},
            wall_s=build_s, backend=jax.default_backend())

    def _pace_dispatch(self, t0: float) -> None:
        """serve.step_floor_ms pacing: sleep out the residual so the
        dispatch takes at least the floor. Runs AFTER block_until_ready
        — the device program is untouched; the sleep releases the GIL
        (and the core), which is the point: it rate-limits this replica
        without burning CPU. No-op at the default 0."""
        floor_s = self.serve.step_floor_ms / 1000.0
        if floor_s <= 0.0:
            return
        residual = floor_s - (time.perf_counter() - t0)
        if residual > 0.0:
            time.sleep(residual)

    def health_snapshot(self) -> dict:
        """JSON progress facts for /healthz (obs/server.py's provider
        contract): the dispatch heartbeat age, queue depth, step debt,
        brownout level, the drain state machine's state, and the live
        model version — enough for a probe to tell wedged from idle, and
        for the fleet router (serve/router.py) to run least-step-debt
        dispatch and drain detection without scraping Prometheus.

        `serve_state` ∈ ok|draining|stopped is the PR 11 state machine's
        position (`status` keeps carrying the same value — it predates
        the router and external probes key on it). `slo_fast_burn` rides
        along when the service scores an SLO (serve.slo.targets): the
        worst per-class fast-window burn rate, the number the rolling-
        deploy gate (serve/deploy.py) watches during canary probation.
        """
        with self._lock:
            depth = len(self._queue)
            debt = self._step_debt_locked()
            level = self._brownout_level
        state = ("stopped" if self._worker is None
                 else "draining" if self._draining else "ok")
        snap = {
            "status": state,
            "serve_state": state,
            "role": "serve",
            "dispatches": int(self.dispatches),
            "queue_depth": depth,
            "step_debt": int(debt),
            "brownout_level": int(level),
            "last_dispatch_age_s": round(
                time.time() - self._last_dispatch_t, 3),
            "model_version": self.model_version,
            # Program builds since boot: the fleet chaos drills assert
            # this stays flat on SURVIVORS across kills/restarts (warm
            # traffic never recompiles) without scraping Prometheus.
            "programs_built": int(self._programs.builds),
        }
        if self._cond_cache:
            # Replica health gains the cache's hit/miss/residency facts
            # so the fleet router (and a probe) can see cache health
            # without scraping Prometheus.
            snap["cond_cache"] = self._cond_cache_stats()
        if self.slo is not None:
            slo_snap = self.slo.snapshot()
            burns = [c.get("fast_burn", 0.0) for c in slo_snap.values()]
            snap["slo_fast_burn"] = round(max(burns), 3) if burns else 0.0
            snap["slo_breached"] = any(
                c.get("breached") for c in slo_snap.values())
            # Gray-failure gauge: the fleet router demotes a replica
            # whose p99 drifts far above its peers' (slow-but-alive).
            snap["latency_p99_s"] = round(self.slo.latency_p99(), 6)
        return snap

    def _cache_key(self, bucket: int, H: int, W: int, steps: int,
                   w: float) -> tuple:
        """Full program-cache key: the per-request shape/steps/guidance
        knobs PLUS every DiffusionConfig field the compiled sampler bakes
        in (sampler, cfg_rescale, ddim_eta, objective, schedule). The
        config fields are constant for one service instance today, but
        keying on them keeps the cache correct if per-request overrides
        are ever extended to cover them. Precision and the fused-step
        flag fold in for the same reason (they change the lowered
        program: in-jit dequant / the Pallas kernel call)."""
        d = self.diffusion
        return (bucket, H, W, steps, w, d.sampler, d.cfg_rescale,
                d.ddim_eta, d.objective, d.schedule,
                self.precision, d.fused_step)

    def _build_program(self, steps: int, w: float):
        import dataclasses

        dcfg = self.diffusion
        if w != dcfg.guidance_weight:
            dcfg = dataclasses.replace(dcfg, guidance_weight=w)
        schedule = sampling_schedule(dcfg, steps)
        return make_request_sampler(self.model, schedule, dcfg,
                                    param_transform=self._param_transform)

    def _dispatch(self, group: List[_Request]) -> None:
        self.dispatches += 1
        self._last_dispatch_t = time.time()
        if self._profiler is not None:
            self._profiler.on_step(self.dispatches)
        faultinject.maybe_serve_dispatch_raise(self.dispatches)
        n = len(group)
        bucket = bucket_for(n, self.serve.max_batch)
        H, W, steps, w = group[0].program_key
        # One consistent (params, version) pair for the WHOLE dispatch:
        # a swap landing mid-flight flips _live but this batch finishes —
        # and is attributed — on the version it started with.
        params, version = self._live
        # Pad rows repeat the LAST request (any valid row works — per-
        # sample RNG streams make rows independent); their outputs are
        # dropped below. Pad keys are zeros: never read by real rows.
        pad = bucket - n
        with self.tracer.span("batch_form", bucket=bucket, batch_n=n):
            cond = {
                k: np.stack([r.cond[k] for r in group]
                            + [group[-1].cond[k]] * pad)
                for k in COND_KEYS
            }
            keys = np.stack([r.key for r in group]
                            + [np.zeros_like(group[-1].key)] * pad)
            if mesh_lib.divides_data_axis(self.mesh, bucket):
                cond_dev = mesh_lib.shard_batch(self.mesh, cond)
                keys_dev = mesh_lib.shard_batch(self.mesh, keys)
            elif self.mesh is not None:
                # Ragged bucket (doesn't divide the 'data' axis):
                # replicate the batch over the mesh. Params are committed
                # to the mesh's device set, so a single-device put here
                # would make jit reject the mixed placement; replicated
                # compute is merely wasteful.
                rep = mesh_lib.replicated(self.mesh)
                cond_dev = jax.device_put(cond, rep)
                keys_dev = jax.device_put(keys, rep)
            else:
                dev = jax.devices()[0]
                cond_dev = jax.device_put(cond, dev)
                keys_dev = jax.device_put(keys, dev)
            entry = self._programs.get(
                self._cache_key(bucket, H, W, steps, w), steps, w)
        cold = not entry["warm"]
        t_disp = time.monotonic()
        t0 = time.perf_counter()
        imgs = np.asarray(jax.device_get(
            entry["fn"](params, keys_dev, cond_dev)))
        self._pace_dispatch(t0)
        elapsed = time.perf_counter() - t0
        entry["warm"] = True
        span = "compile" if cold else "device"
        for r in group:
            r.swap_drains = self._swaps - r.swaps_at_submit
            r.rides += 1
        self.tracer.add_span(span, elapsed, bucket=bucket, batch_n=n,
                             model_version=version,
                             dispatch=self.dispatches,
                             riders=",".join(str(r.ticket.request_id)
                                             for r in group))
        with self.tracer.span("respond", batch_n=n,
                              model_version=version):
            for i, r in enumerate(group):
                timing = {
                    "queue_wait_s": max(0.0, t_disp - r.t_submit),
                    f"{span}_s": elapsed,
                    "bucket": bucket,
                    "batch_n": n,
                    "model_version": version,
                }
                r.ticket.model_version = version
                self.stats.record_span("queue_wait",
                                       timing["queue_wait_s"])
                self.stats.record_span(span, elapsed)
                self.tracer.add_span(
                    "queue_wait", timing["queue_wait_s"],
                    request_id=r.ticket.request_id,
                    trace_id=r.trace_id,
                    parent_id=reqtrace.root_span_id(r.trace_id))
                r.ticket._resolve(imgs[i], timing)
                self._respond_span(r, "ok", steps_done=int(steps))
        self.stats.count_requests(n)
        self._requests_total.inc(n)


def request_cond_from_batch(batch: Dict[str, np.ndarray],
                            i: int = 0) -> Dict[str, np.ndarray]:
    """Unbatched request conditioning from row i of a batched cond dict
    (test/bench convenience)."""
    return {k: np.asarray(batch[k])[i] for k in COND_KEYS}
