"""Training step factory + CLI driver.

``make_train_step(cfg, ...)`` returns a pure (state, batch) -> (state,
metrics) function:

* gradient accumulation over ``grad_accum`` microbatches via lax.scan — the
  logits tensor (the memory peak at 128k-vocab) only ever materializes per
  microbatch;
* grads accumulated in ``grad_dtype`` (bf16 at 405B scale, fp32 below);
* AdamW with ZeRO-1-sharded moments (shard.moment_specs);
* optional int8 gradient compression with error feedback (optim.compress).

CLI:  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
          --steps 100 --batch 8 --seq 256   (runs on whatever devices exist)
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get as get_cfg
from repro.configs.base import ArchConfig
from repro.models import api
from repro.optim.adamw import AdamWConfig, adamw_update, init_moments


def make_train_step(cfg: ArchConfig, opt_cfg: AdamWConfig = AdamWConfig(),
                    grad_accum: int = 1, grad_dtype: str = "float32",
                    grad_sync: str = "auto", mesh=None):
    """grad_sync:
    "auto" — GSPMD decides; XLA all-reduces weight grads once per MICROBATCH
             inside the accumulation scan (measured §Perf).
    "late" — the microbatch loop runs inside shard_map over the data axes
             (model axis stays auto/GSPMD): grads accumulate locally and are
             psum'd ONCE per step — grad-sync collective bytes / grad_accum.
             Requires ``mesh``.
    """
    gdt = jnp.dtype(grad_dtype)

    def loss(params, mb):
        return api.loss_fn(cfg, params, mb)

    def accum_grads(params, micro):
        def body(acc, mb):
            l, g = jax.value_and_grad(loss)(params, mb)
            acc_g, acc_l = acc
            acc_g = jax.tree.map(
                lambda a, b: (a + b.astype(gdt)).astype(gdt), acc_g, g)
            return (acc_g, acc_l + l), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, gdt), params)
        (grads, lsum), _ = jax.lax.scan(body, (g0, 0.0), micro)
        return (jax.tree.map(lambda g: g / grad_accum, grads),
                lsum / grad_accum)

    def split_batch(x):
        from repro.nn.layers import constrain

        y = x.reshape(grad_accum, x.shape[0] // grad_accum, *x.shape[1:])
        # keep every microbatch batch-sharded over dp: without this XLA
        # factors the dp axis across the microbatch-index dim and the scan
        # gathers each slice (§Perf iteration 3)
        return constrain(y, None, "dp", *([None] * (y.ndim - 2)))

    if grad_sync == "late":
        if mesh is None:
            raise ValueError("grad_sync='late' needs the mesh")
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import data_axes

        dp = data_axes(mesh)
        # each microbatch must still split across the data axes: shard_map
        # asserts the divisibility at trace time

        def grad_fn(params, micro_local):
            g, l = accum_grads(params, micro_local)
            # THE one grad sync per step (vs one per microbatch under GSPMD)
            g = jax.tree.map(lambda x: jax.lax.pmean(x, dp), g)
            return g, jax.lax.pmean(l, dp)

        def late_grads(params, batch):
            micro = jax.tree.map(
                lambda x: x.reshape(grad_accum, x.shape[0] // grad_accum,
                                    *x.shape[1:]), batch)
            in_specs = (jax.tree.map(lambda _: P(), params),
                        jax.tree.map(lambda x: P(None, dp), micro))
            out_specs = (jax.tree.map(lambda _: P(), params), P())
            fn = jax.shard_map(grad_fn, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs, axis_names=set(dp),
                               check_vma=False)
            return fn(params, micro)

    def train_step(state, batch):
        params, opt = state["params"], state["opt"]
        if grad_accum > 1 and grad_sync == "late":
            grads, lval = late_grads(params, batch)
        elif grad_accum > 1:
            micro = jax.tree.map(split_batch, batch)
            grads, lval = accum_grads(params, micro)
        else:
            lval, grads = jax.value_and_grad(loss)(params, batch)
        new_params, new_opt = adamw_update(params, grads, opt, opt_cfg)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree.leaves(grads)))
        return ({"params": new_params, "opt": new_opt},
                {"loss": lval, "grad_norm": gnorm, "step": new_opt["step"]})

    return train_step


def _dp_size(mesh) -> int:
    from repro.launch.mesh import data_axes, mesh_dims

    md = mesh_dims(mesh)
    n = 1
    for a in data_axes(mesh):
        n *= md[a]
    return n


def init_state(cfg: ArchConfig, opt_cfg: AdamWConfig = AdamWConfig(), rng=None):
    params = api.init_params(cfg, rng)
    return {"params": params, "opt": init_moments(params, opt_cfg)}


def abstract_state(cfg: ArchConfig, opt_cfg: AdamWConfig = AdamWConfig()):
    return jax.eval_shape(lambda: init_state(cfg, opt_cfg))


def state_specs(state_abstract, mesh):
    """Sharding specs for the full train state (params TP, moments ZeRO-1)."""
    from repro.launch import shard

    return {
        "params": shard.param_specs(state_abstract["params"], mesh),
        "opt": {
            "m": shard.moment_specs(state_abstract["opt"]["m"], mesh),
            "v": shard.moment_specs(state_abstract["opt"]["v"], mesh),
            "step": jax.sharding.PartitionSpec(),
        },
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    args = ap.parse_args()

    cfg = get_cfg(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    from repro.data.pipeline import SyntheticLM

    data = SyntheticLM(vocab=cfg.vocab, batch=args.batch, seq=args.seq,
                       family=cfg.family, d_model=cfg.d_model,
                       n_patches=cfg.n_patches)
    state = init_state(cfg)
    step_fn = jax.jit(make_train_step(cfg, grad_accum=args.grad_accum),
                      donate_argnums=(0,))
    ckpt = None
    if args.checkpoint_dir:
        from repro.checkpoint.store import CheckpointStore

        ckpt = CheckpointStore(args.checkpoint_dir)
        restored = ckpt.restore_latest(jax.eval_shape(lambda: state))
        if restored is not None:
            state, start = restored
            data.seek(start)
            print(f"restored checkpoint at step {start}")
    t0 = time.perf_counter()
    for i in range(args.steps):
        state, metrics = step_fn(state, data.next())
        if (i + 1) % 10 == 0:
            l = float(metrics["loss"])
            dt = (time.perf_counter() - t0) / (i + 1)
            print(f"step {i+1:5d} loss {l:.4f}  {dt*1e3:.1f} ms/step")
        if ckpt and (i + 1) % args.checkpoint_every == 0:
            ckpt.save(state, step=i + 1, async_write=True)
    if ckpt:
        ckpt.save(state, step=args.steps)
        ckpt.wait()
    print(f"final loss {float(metrics['loss']):.4f}")


if __name__ == "__main__":
    main()
