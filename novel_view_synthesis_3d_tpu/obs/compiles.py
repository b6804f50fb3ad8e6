"""Compile ledger + per-op cost map (docs/DESIGN.md "Training numerics
& compile observatory").

Every jit build across train/serve/bench records an entry in
``compiles.jsonl``: a fingerprint (arg shapes/dtypes + a static-config
digest), compile wall time, and an HLO module hash. A rebuild under the
SAME name with a DIFFERENT fingerprint is a recompile: the ledger diffs
against the prior fingerprint and logs WHICH argument changed — the
answer `nvs3d obs compiles --why N` renders and serve_bench's
zero-recompile asserts print on failure. This module is the only place
that names ``compiles.jsonl`` / ``costmap.json`` (the events.csv
conformance convention).

``xunet_costmap`` is the one-time per-op cost model: lower each op of
the op-sliced XUNet (models/xunet.pipeline_op_specs) on abstract shapes
and read XLA's cost_analysis — per-op FLOPs/bytes with NO XLA compile
and no device work, keyed by the same group labels the numerics
observatory uses.

No jax at module load (supervisor constraint); traced helpers import it
lazily.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from typing import Dict, List, Optional, Sequence

_COMPILES_FILE = "compiles.jsonl"
_COSTMAP_FILE = "costmap.json"


def compiles_path(results_folder: str) -> str:
    return os.path.join(results_folder, _COMPILES_FILE)


def costmap_path(results_folder: str) -> str:
    return os.path.join(results_folder, _COSTMAP_FILE)


def static_digest(obj) -> str:
    """Short stable digest of a build's static configuration (anything
    with a deterministic repr — config dataclasses, cache-key tuples)."""
    return hashlib.sha256(repr(obj).encode()).hexdigest()[:12]


def hlo_hash(lowered) -> str:
    """Short hash of a lowered computation's HLO text ("" when the
    lowering cannot render — never fatal, the ledger entry just goes
    unhashed)."""
    try:
        return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:12]
    except Exception:
        return ""


def fingerprint_args(*args, static=None) -> dict:
    """Build a ledger fingerprint from a jit call's arguments.

    {"args": {leaf path: "dtype[shape]"}, "static": digest}. Leaves are
    described by shape/dtype only (values never enter the ledger), so
    two calls fingerprint equal exactly when XLA would reuse the cached
    executable for them.
    """
    import jax

    described: Dict[str, str] = {}
    for i, arg in enumerate(args):
        flat = jax.tree_util.tree_flatten_with_path(arg)[0]
        for path, leaf in flat:
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            desc = (f"{dtype}{list(shape)}"
                    if shape is not None and dtype is not None
                    else repr(leaf)[:64])
            described[f"arg{i}{jax.tree_util.keystr(path)}"] = desc
    fp = {"args": described}
    if static is not None:
        fp["static"] = static_digest(static)
    return fp


def fingerprint_diff(old: dict, new: dict) -> List[str]:
    """Human-readable lines naming what changed between fingerprints —
    the recompile culprit."""
    lines: List[str] = []
    o_args, n_args = old.get("args", {}), new.get("args", {})
    for key in sorted(set(o_args) | set(n_args)):
        if key not in n_args:
            lines.append(f"{key}: {o_args[key]} -> (removed)")
        elif key not in o_args:
            lines.append(f"{key}: (new) -> {n_args[key]}")
        elif o_args[key] != n_args[key]:
            lines.append(f"{key}: {o_args[key]} -> {n_args[key]}")
    if old.get("static", "") != new.get("static", ""):
        lines.append(f"static digest: {old.get('static', '')} -> "
                     f"{new.get('static', '')}")
    return lines


class CompileLedger:
    """Append-only record of jit builds for one results folder.

    Thread-safe (the serving plane builds programs from worker threads).
    `record` returns the entry it wrote; a recompile entry carries
    `diff` (the fingerprint delta) and `changed` (the first diff line —
    the one-line culprit)."""

    def __init__(self, results_folder: str, registry=None):
        self.results_folder = results_folder
        self._lock = threading.Lock()
        self._by_name: Dict[str, dict] = {}
        self.entries: List[dict] = []
        self._counter = (registry.counter(
            "nvs3d_compiles_total",
            "jit builds recorded in the compile ledger")
            if registry is not None else None)

    def record(self, name: str, fingerprint: dict, *,
               wall_s: Optional[float] = None, hlo: str = "",
               backend: str = "") -> dict:
        entry = {"kind": "compile", "name": name, "t": round(time.time(), 3),
                 "fingerprint": fingerprint}
        if wall_s is not None:
            entry["wall_s"] = round(float(wall_s), 3)
        if hlo:
            entry["hlo_hash"] = hlo
        if backend:
            entry["backend"] = backend
        with self._lock:
            prev = self._by_name.get(name)
            if prev is not None and prev != fingerprint:
                entry["kind"] = "recompile"
                diff = fingerprint_diff(prev, fingerprint)
                entry["diff"] = diff
                entry["changed"] = diff[0] if diff else "(fingerprint " \
                    "changed but no field-level diff — same shapes, new " \
                    "static digest?)"
            self._by_name[name] = fingerprint
            self.entries.append(entry)
        if self._counter is not None:
            self._counter.inc(name=name, kind=entry["kind"])
        self._append(entry)
        return entry

    def recompiles(self) -> List[dict]:
        with self._lock:
            return [e for e in self.entries if e["kind"] == "recompile"]

    def _append(self, entry: dict) -> None:
        # Open per record: builds are rare by construction — no handle
        # to leak across supervisor generations (the append_event policy).
        try:
            os.makedirs(self.results_folder, exist_ok=True)
            with open(compiles_path(self.results_folder), "a") as fh:
                fh.write(json.dumps(entry) + "\n")
                fh.flush()
        except (OSError, TypeError, ValueError):
            pass  # ledger IO faults are never the run's fault


def load_ledger(results_folder: str) -> List[dict]:
    """Read compiles.jsonl back ([] when absent/empty); skips torn
    trailing lines the way every jsonl consumer here does."""
    path = compiles_path(results_folder)
    if not os.path.exists(path):
        return []
    entries = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                entries.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return entries


def last_recompile(results_folder: str) -> Optional[dict]:
    """Newest recompile entry on disk — what a zero-recompile assert
    prints as the culprit. None when the ledger records no recompile."""
    found = None
    for entry in load_ledger(results_folder):
        if entry.get("kind") == "recompile":
            found = entry
    return found


# ---------------------------------------------------------------------
# Per-op cost map
# ---------------------------------------------------------------------
def xunet_costmap(config, model_batch) -> List[dict]:
    """One-time per-op FLOPs/bytes table over the op-sliced XUNet.

    `model_batch` supplies SHAPES only (the trainer's _sample_model_batch
    projection of any train batch). Each op is lowered in isolation —
    ops=(i, i+1) with the carry threaded through jax.eval_shape — and
    costed with XLA's lowered cost_analysis: a trace per op, no XLA
    compile, no device execution. Rows carry the numerics group label so
    a sentry trip and a grad-norm spike name ops the same way.
    """
    import jax

    from novel_view_synthesis_3d_tpu.models import (
        build_denoiser, require_family)
    from novel_view_synthesis_3d_tpu.models.xunet import (
        op_groups, pipeline_op_specs)

    require_family(
        config.model, "xunet", "obs.compiles' per-op cost table",
        "an op-sliced call (`ops=(a, b)`) of the token trunk's layers")
    model = build_denoiser(config.model)
    specs = pipeline_op_specs(config.model)
    labels = [label for label, _ in op_groups(config.model)]

    def struct(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    import numpy as np

    batch_s = struct(model_batch)
    B = model_batch["z"].shape[0]
    mask_s = jax.ShapeDtypeStruct((B,), np.float32)
    # Wrapped so `train` stays a Python constant (eval_shape would trace
    # a bare keyword into an abstract bool and break flax's branching).
    params_s = struct(jax.eval_shape(
        lambda b, m: model.init(jax.random.PRNGKey(0), b,
                                cond_mask=m, train=False),
        batch_s, mask_s))

    rows: List[dict] = []
    carry_s = None
    for i, (kind, info) in enumerate(specs):
        def op_fwd(variables, batch, cond_mask, carry, _i=i):
            return model.apply(variables, batch, cond_mask=cond_mask,
                               train=False, ops=(_i, _i + 1), carry=carry)

        lowered = jax.jit(op_fwd).lower(params_s, batch_s, mask_s, carry_s)
        ca = lowered.cost_analysis()
        # Return shape varies across JAX versions (list → dict); the
        # legacy list is a refusal, not a compat path (bench._cost_numbers
        # has the full rationale).
        if isinstance(ca, dict):
            flops = float(ca.get("flops", 0.0)) or None
            byts = float(ca.get("bytes accessed", 0.0)) or None
        else:
            flops, byts = None, None
        rows.append({"op": i, "kind": kind,
                     "name": info.get("name", kind),
                     "group": labels[i], "flops": flops, "bytes": byts})
        if i + 1 < len(specs):
            carry_s = jax.eval_shape(op_fwd, params_s, batch_s, mask_s,
                                     carry_s)
    return rows


def write_costmap(results_folder: str, rows: Sequence[dict]) -> str:
    """Persist the cost map next to the run's other telemetry; returns
    the path. Kept here so producers (bench) never name the file."""
    os.makedirs(results_folder, exist_ok=True)
    path = costmap_path(results_folder)
    with open(path, "w") as fh:
        json.dump({"ops": list(rows)}, fh, indent=2)
        fh.write("\n")
    return path


def load_costmap(results_folder: str) -> List[dict]:
    path = costmap_path(results_folder)
    if not os.path.exists(path):
        return []
    try:
        with open(path) as fh:
            doc = json.load(fh)
    except (OSError, json.JSONDecodeError):
        return []
    return list(doc.get("ops", []))
