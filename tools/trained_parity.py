"""Trained-weight parity: train the `reference` preset, export, and verify
forward parity against the ACTUAL reference model source (VERDICT r2 item 6).

The golden tests (tests/test_reference_ckpt.py) pin parity at *random init*;
init-scale weights can hide drift in branches that only matter once weights
leave the init distribution (e.g. GroupNorm statistics interacting with
grown activations, attention logit scales). So: train this repo's model a
few hundred steps, `export_reference_params`, feed the exported tree to the
reference's own `model/xunet.py` (run under current flax with the visu3d
shim from tools/make_reference_goldens.py), and require the two models to
agree on a fixed batch to float tolerance.

Writes results/parity_r03/trained_parity.json (steps, loss curve endpoints,
max abs/rel forward deviation) and a fresh golden
tests/golden/reference_xunet_trained.npz so the parity-on-trained-weights
claim stays testable WITHOUT the reference checkout.

Usage: python tools/trained_parity.py [steps]   (default 300; CPU-friendly,
16px inputs like the goldens)
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

OUT_DIR = os.path.join(REPO, "results", "parity_r03")
GOLDEN_OUT = os.path.join(REPO, "tests", "golden",
                          "reference_xunet_trained.npz")


def main() -> None:
    steps = int(sys.argv[1]) if len(sys.argv) > 1 else 300

    from _common import init_jax_env
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    init_jax_env()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import make_reference_goldens as mrg
    from novel_view_synthesis_3d_tpu.compat.reference_ckpt import (
        export_reference_params)
    from novel_view_synthesis_3d_tpu.config import get_preset
    from novel_view_synthesis_3d_tpu.data.synthetic import make_example_batch
    from novel_view_synthesis_3d_tpu.diffusion import make_schedule
    from novel_view_synthesis_3d_tpu.models import build_denoiser
    from novel_view_synthesis_3d_tpu.parallel import mesh as mesh_lib
    from novel_view_synthesis_3d_tpu.train.state import create_train_state
    from novel_view_synthesis_3d_tpu.train.step import make_train_step
    from novel_view_synthesis_3d_tpu.train.trainer import _sample_model_batch

    # --- train the reference-preset model on 16px synthetic batches -------
    cfg = get_preset("reference").override(**{
        "data.img_sidelength": 16,
        "train.batch_size": 8,
        "train.num_steps": steps,
        # Plain SGD-shaped run: EMA off so the exported tree is exactly the
        # online params the loss curve describes.
        "train.ema_decay": 0.0,
    })
    cfg.validate()
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(data=1, model=1, seq=1),
                              devices=jax.devices()[:1])
    schedule = make_schedule(cfg.diffusion)
    model = build_denoiser(cfg.model)
    batch = make_example_batch(batch_size=cfg.train.batch_size,
                               sidelength=16, seed=0)
    state = create_train_state(cfg.train, model, _sample_model_batch(batch))
    state = mesh_lib.replicate(mesh, state)
    step = make_train_step(cfg, model, schedule, mesh)
    losses = []
    t0 = time.time()
    for i in range(steps):
        # Fresh synthetic batch per step so the weights travel a real
        # optimization trajectory instead of memorizing one batch.
        b = make_example_batch(batch_size=cfg.train.batch_size,
                               sidelength=16, seed=i)
        state, m = step(state, mesh_lib.shard_batch(mesh, b))
        if i % 25 == 0 or i == steps - 1:
            loss = float(jax.device_get(m["loss"]))
            losses.append((i, loss))
            print(f"step {i}: loss {loss:.4f}", flush=True)
    train_s = time.time() - t0
    params = jax.device_get(state.params)

    # --- export to reference format, run the reference source on it -------
    exported = export_reference_params(params)
    mrg._install_visu3d_shim()
    ref = mrg._load_reference_model()
    ref_model = ref.XUNet()  # reference defaults == `reference` preset
    eval_batch = mrg.make_batch(B=2, S=16, seed=123)
    cond_mask = np.array([1.0, 0.0], np.float32)
    jb = {k: jnp.asarray(v) for k, v in eval_batch.items()}
    ref_out = np.asarray(ref_model.apply(
        {"params": jax.tree.map(jnp.asarray, exported)}, jb,
        cond_mask=jnp.asarray(cond_mask), train=False))
    our_out = np.asarray(model.apply(
        {"params": jax.tree.map(jnp.asarray, params)}, jb,
        cond_mask=jnp.asarray(cond_mask), train=False))

    abs_dev = float(np.max(np.abs(ref_out - our_out)))
    rel_dev = float(np.max(np.abs(ref_out - our_out) /
                           (np.abs(ref_out) + 1e-6)))
    scale = float(np.max(np.abs(ref_out)))
    # Scale-aware bound: element-wise rtol alone rejects float-reassociation
    # noise at near-zero outputs (FrameConv reduces in a different order
    # than the reference's 3-D conv), so compare against the OUTPUT SCALE:
    # 1e-4 × max|out| is ~10 float32 ulps of the largest activation.
    ok = bool(abs_dev <= 1e-4 * scale)
    print(f"trained-weight parity: max|Δ|={abs_dev:.3e} "
          f"(output scale {scale:.3e}), max rel={rel_dev:.3e}, ok={ok}")

    # --- persist: JSON artifact + a trained golden for offline testing ----
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "trained_parity.json"), "w") as fh:
        json.dump({
            "steps": steps,
            "train_seconds": round(train_s, 1),
            "loss_first": losses[0][1],
            "loss_last": losses[-1][1],
            "max_abs_deviation": abs_dev,
            "max_rel_deviation": rel_dev,
            "output_scale": scale,
            "parity_ok": ok,
            "platform": jax.default_backend(),
        }, fh, indent=1)

    if not ok:
        # Do NOT touch the committed golden on failure: a drifted npz in
        # the working tree could ride along into an unrelated commit. The
        # JSON diagnostic above is the failure record.
        raise SystemExit("PARITY FAILURE on trained weights — golden NOT "
                         "rewritten")

    flat = {}
    def flatten(tree, prefix=""):
        for k, v in tree.items():
            p = f"{prefix}/{k}" if prefix else k
            if isinstance(v, dict):
                flatten(v, p)
            else:
                flat[f"param:{p}"] = np.asarray(v)
    flatten(exported)
    arrays = dict(flat)
    for k, v in eval_batch.items():
        arrays[f"batch:{k}"] = v
    arrays["cond_mask"] = cond_mask
    arrays["output"] = ref_out  # the REFERENCE source's output
    np.savez_compressed(GOLDEN_OUT, **arrays)
    print(f"wrote {GOLDEN_OUT} "
          f"({os.path.getsize(GOLDEN_OUT) / 1e6:.2f} MB)")


if __name__ == "__main__":
    main()
