"""Common layers: norms, rotary embeddings (incl. M-RoPE), MLPs, MoE."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _mesh_dims():
    """{axis name: size} of the mesh in effect, or None."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return None
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def constrain(x, *logical):
    """Megatron-style activation sharding constraint.

    ``logical`` entries: "dp" (batch over pod+data axes), "tp" (the model
    axis), None.  No-op outside a mesh context or when a dim is not
    divisible — so the same model code runs in smoke tests (1 device) and on
    the production mesh.  Added in §Perf iteration 1: without these, XLA's
    propagation all-gathers full fp32 FFN hiddens every layer
    (EXPERIMENTS.md §Perf).
    """
    import os

    if os.environ.get("REPRO_NO_CONSTRAIN"):  # baseline-measurement switch
        return x
    dims = _mesh_dims()
    if dims is None:
        return x
    spec = []
    for d, s in zip(x.shape, logical):
        if s == "dp":
            axes = tuple(a for a in ("pod", "data") if a in dims)
            size = 1
            for a in axes:
                size *= dims[a]
            spec.append(axes if axes and d % size == 0 and d >= size else None)
        elif s == "tp":
            ok = "model" in dims and d % dims["model"] == 0 and d >= dims["model"]
            spec.append("model" if ok else None)
        else:
            spec.append(None)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def rms_norm(x, gamma, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * gamma.astype(x.dtype)


def layer_norm(x, gamma, beta, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y.astype(x.dtype) * gamma.astype(x.dtype)) + beta.astype(x.dtype)


# ---------------------------------------------------------------- rotary
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float = 1e6):
    """x (..., S, H, D); positions (..., S) int32."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)                     # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, D/2)
    cos = jnp.cos(ang)[..., None, :]               # (..., S, 1, D/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta: float = 1e6, sections=(1, 1, 2)):
    """M-RoPE (Qwen2-VL): the head_dim/2 frequency bands are split into
    temporal/height/width sections, each rotated by its own position id.

    x (..., S, H, D); positions3 (3, ..., S)."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)                     # (D/2,)
    n = inv.shape[0]
    w = jnp.array(sections, jnp.float32)
    bounds = jnp.cumsum(w) / jnp.sum(w) * n
    idx = jnp.arange(n)
    sec = (idx[None, :] < bounds[:, None]).astype(jnp.float32)
    sec = sec.at[1:].set(sec[1:] - sec[:-1])       # one-hot per section (3, D/2)
    pos = positions3[..., None].astype(jnp.float32)        # (3, ..., S, 1)
    ang = jnp.einsum("k...sf,kf->...sf", pos * inv, sec)   # mix per section
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------- MLPs
def mlp(x, p, act: str):
    if act == "silu_gated":
        h = jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])
    else:
        h = jax.nn.gelu(x @ p["w1"])
    h = constrain(h, "dp", None, "tp")      # keep hidden model-sharded
    return constrain(h @ p["w2"], "dp", None, None)


def moe_mlp(x, p, act: str, top_k: int = 2):
    """Dense-dispatch top-k MoE: every expert sees every token, weighted by
    the (zeroed for non-selected) router probabilities.

    On a 16-way model axis with 8 experts, expert-parallel sharding would
    idle half the axis; instead experts stay local and each expert's d_ff is
    TP-sharded ("horizontal fusion" of experts sharing the same input — the
    paper's §4.1.3 template in transformer clothing; DESIGN.md §5)."""
    b, s, d = x.shape
    e = p["w1"].shape[0]
    logits = x @ p["router"]                                # (B,S,E)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    vals, idxs = jax.lax.top_k(probs, top_k)                # (B,S,k)
    vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    gate = jnp.zeros_like(probs).astype(x.dtype)
    gate = jax.vmap(lambda g, i, v: g.at[i].set(v), in_axes=(0, 0, 0))(
        gate.reshape(b * s, e), idxs.reshape(b * s, top_k),
        vals.astype(x.dtype).reshape(b * s, top_k)).reshape(b, s, e)
    h1 = jnp.einsum("bsd,edf->bsef", x, p["w1"])
    if act == "silu_gated":
        h = jax.nn.silu(h1) * jnp.einsum("bsd,edf->bsef", x, p["w3"])
    else:
        h = jax.nn.gelu(h1)
    h = constrain(h, "dp", None, None, "tp")
    y = jnp.einsum("bsef,efd->bsed", h, p["w2"])
    out = constrain(jnp.einsum("bsed,bse->bsd", y, gate), "dp", None, None)
    aux = _load_balance_loss(probs, idxs, e)
    return out, aux


def _load_balance_loss(probs, idxs, n_experts: int):
    """Switch-style auxiliary load-balancing loss."""
    me = jnp.mean(probs, axis=(0, 1))                       # (E,)
    ce = jnp.mean(jax.nn.one_hot(idxs[..., 0], n_experts), axis=(0, 1))
    return n_experts * jnp.sum(me * ce)
