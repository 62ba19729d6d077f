"""Execution of a compiled strategy as JAX callables (runtime support, §3.2).

Three backends:

* ``float``      — float32 reference semantics (calibration + accuracy oracle);
* ``int8_ref``   — pure-jnp fixed-point semantics from ``int8_ops`` (the
  validation oracle; bit-exact by definition);
* ``int8_pallas``— dispatches the compile-time lowered ``GroupProgram``
  (``core.lower``): every ``FusedLaunch`` runs as ONE ``kernels.conv_fused``
  chain launch (LOAD->CONV->MISC->SAVE on-chip, the paper's fusion), every
  ``RefFallback`` runs its nodes through the ref ops.  The executor performs
  ZERO runtime graph pattern matching — lowering decided everything once.
  The contract — enforced by validate.py and the kernel tests — is
  bit-exactness with ``int8_ref``.

Mixed compilation (paper §2.3.5): nodes partitioned to the host execute as
plain float ops on dequantized inputs (softmax & friends) and appear in the
program as ``RefFallback("host_op")`` entries.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import int8_ops
from repro.core.quantize import QuantizedModel
from repro.core.xgraph import XGraph, _padding


# ------------------------------------------------------------------ float ref
def _float_node(g: XGraph, node, env, params):
    a = node.attrs
    op = node.op
    xs = [env[i] for i in node.inputs]
    if op in ("conv", "dilated_conv", "depthwise_conv"):
        kh, kw = a["kernel"]
        dil = a.get("dilation", (1, 1))
        ph, pw = _padding(a.get("pad", "same"), dil[0] * (kh - 1) + 1,
                          dil[1] * (kw - 1) + 1)
        w = params[node.name]["w"]
        b = params[node.name].get("b", np.zeros(w.shape[-1], np.float32))
        groups = xs[0].shape[-1] if op == "depthwise_conv" else 1
        y = jax.lax.conv_general_dilated(
            xs[0], jnp.asarray(w), a.get("stride", (1, 1)),
            [(ph, ph), (pw, pw)], rhs_dilation=dil,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups) + jnp.asarray(b)
    elif op == "fc":
        w = params[node.name]["w"]
        b = params[node.name].get("b", np.zeros(w.shape[-1], np.float32))
        n = xs[0].shape[0]
        y = (xs[0].reshape(n, -1) @ jnp.asarray(w) + jnp.asarray(b)).reshape(
            n, 1, 1, -1)
    elif op == "maxpool":
        kh, kw = a["kernel"]
        sh, sw = a.get("stride", a["kernel"])
        ph, pw = _padding(a.get("pad", "valid"), kh, kw)
        oh = g.shape(node.name)[1]
        ow = g.shape(node.name)[2]
        h, w_ = xs[0].shape[1:3]
        eh = max(0, (oh - 1) * sh + kh - h - 2 * ph)
        ew = max(0, (ow - 1) * sw + kw - w_ - 2 * pw)
        y = jax.lax.reduce_window(
            xs[0], -jnp.inf, jax.lax.max, (1, kh, kw, 1), (1, sh, sw, 1),
            ((0, 0), (ph, ph + eh), (pw, pw + ew), (0, 0)))
    elif op == "avgpool":
        kh, kw = a["kernel"]
        sh, sw = a.get("stride", a["kernel"])
        ph, pw = _padding(a.get("pad", "valid"), kh, kw)
        oh, ow = g.shape(node.name)[1:3]
        h, w_ = xs[0].shape[1:3]
        eh = max(0, (oh - 1) * sh + kh - h - 2 * ph)
        ew = max(0, (ow - 1) * sw + kw - w_ - 2 * pw)
        y = jax.lax.reduce_window(
            xs[0], 0.0, jax.lax.add, (1, kh, kw, 1), (1, sh, sw, 1),
            ((0, 0), (ph, ph + eh), (pw, pw + ew), (0, 0))) / (kh * kw)
    elif op == "global_avgpool":
        y = jnp.mean(xs[0], axis=(1, 2), keepdims=True)
    elif op == "eltwise_add":
        y = sum(xs)
    elif op == "concat":
        y = jnp.concatenate(xs, axis=-1)
    elif op == "upsample":
        y = int8_ops.upsample(xs[0], a.get("factor", 2))
    elif op == "reorg":
        y = int8_ops.reorg(xs[0], a.get("stride", 2))
    elif op == "softmax":
        y = jax.nn.softmax(xs[0], axis=-1)
    elif op == "deconv":
        w = params[node.name]["w"]
        b = params[node.name].get("b", np.zeros(w.shape[-1], np.float32))
        y = jax.lax.conv_transpose(
            xs[0], jnp.asarray(w), a.get("stride", (2, 2)), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + jnp.asarray(b)
    else:
        raise ValueError(f"float executor: unknown op {op}")
    if a.get("relu"):
        y = jax.nn.relu(y)
    return y


def run_float(g: XGraph, params: dict, x: np.ndarray) -> dict:
    """All node activations in float32 (used by calibration)."""

    @jax.jit
    def go(x):
        env = {}
        for node in g:
            if node.op == "input":
                env[node.name] = x
            else:
                env[node.name] = _float_node(g, node, env, params)
        return env

    return {k: np.asarray(v) for k, v in go(jnp.asarray(x, jnp.float32)).items()}


def build_float_fn(g: XGraph, params: dict):
    outputs = [n.name for n in g if not g.consumers(n.name)]

    @jax.jit
    def fn(x):
        env = {}
        for node in g:
            env[node.name] = (x if node.op == "input"
                              else _float_node(g, node, env, params))
        return {o: env[o] for o in outputs}

    return fn


# -------------------------------------------------------------------- int8
def _int8_node(g: XGraph, node, env, qm: QuantizedModel):
    a, op = node.attrs, node.op
    xs = [env[i] for i in node.inputs]
    relu = bool(a.get("relu"))
    if op in ("conv", "dilated_conv"):
        kh, kw = a["kernel"]
        dil = a.get("dilation", (1, 1))
        ph, pw = _padding(a.get("pad", "same"), dil[0] * (kh - 1) + 1,
                          dil[1] * (kw - 1) + 1)
        return int8_ops.conv2d(xs[0], jnp.asarray(qm.weights[node.name]),
                               jnp.asarray(qm.biases[node.name]),
                               stride=a.get("stride", (1, 1)), pad=(ph, pw),
                               dilation=dil, shift=qm.shift_for(g, node.name),
                               relu=relu)
    if op == "depthwise_conv":
        kh, kw = a["kernel"]
        ph, pw = _padding(a.get("pad", "same"), kh, kw)
        return int8_ops.depthwise_conv2d(
            xs[0], jnp.asarray(qm.weights[node.name]),
            jnp.asarray(qm.biases[node.name]), stride=a.get("stride", (1, 1)),
            pad=(ph, pw), shift=qm.shift_for(g, node.name), relu=relu)
    if op == "fc":
        return int8_ops.fc(xs[0], jnp.asarray(qm.weights[node.name]),
                           jnp.asarray(qm.biases[node.name]),
                           shift=qm.shift_for(g, node.name), relu=relu)
    if op == "maxpool":
        kh, kw = a["kernel"]
        ph, pw = _padding(a.get("pad", "valid"), kh, kw)
        return int8_ops.maxpool(xs[0], kernel=a["kernel"],
                                stride=a.get("stride", a["kernel"]),
                                pad=(ph, pw), ceil_mode=a.get("ceil_mode", True))
    if op == "avgpool":
        kh, kw = a["kernel"]
        ph, pw = _padding(a.get("pad", "valid"), kh, kw)
        return int8_ops.avgpool(xs[0], kernel=a["kernel"],
                                stride=a.get("stride", a["kernel"]), pad=(ph, pw),
                                ceil_mode=a.get("ceil_mode", True))
    if op == "global_avgpool":
        return int8_ops.global_avgpool(xs[0])
    if op == "eltwise_add":
        fs = [qm.f_a[i] for i in node.inputs]
        return int8_ops.eltwise_add(xs, fs, qm.f_a[node.name], relu=relu)
    if op == "concat":
        fs = [qm.f_a[i] for i in node.inputs]
        return int8_ops.concat(xs, fs, qm.f_a[node.name])
    if op == "upsample":
        return int8_ops.upsample(xs[0], a.get("factor", 2))
    if op == "reorg":
        return int8_ops.reorg(xs[0], a.get("stride", 2))
    if op == "softmax":  # host op: dequantize, float softmax
        f_in = qm.f_a[node.inputs[0]]
        return jax.nn.softmax(xs[0].astype(jnp.float32) * 2.0 ** -f_in, axis=-1)
    raise ValueError(f"int8 executor: unknown op {op}")


class Int8Executor:
    """Executes a fusion strategy on int8 data.

    backend="ref"    : per-node jnp fixed-point ops (oracle).
    backend="pallas" : dispatches the lowered ``GroupProgram`` — one
                       ``kernels.conv_fused`` chain launch per FusedLaunch
                       (compiled on an accelerator, interpreted on the CPU —
                       ``ops.interpret_mode``), the ref path per RefFallback.
                       Bit-exact with "ref" by contract.
    """

    def __init__(self, g: XGraph, qm: QuantizedModel, strategy=None,
                 backend: str = "ref"):
        """``strategy`` is anything with ``.groups`` / ``.horizontal`` /
        ``.meta`` — a ``pathsearch.Strategy`` or a loaded
        ``asm.CompiledArtifact`` (the plan-cache serving path).  An artifact
        carrying a quantized ``.program`` section is dispatched as-is (no
        re-lowering); otherwise the strategy is lowered here, once, at
        construction time."""
        self.g, self.qm, self.backend = g, qm, backend
        self.groups = None
        self.program = None
        if backend == "pallas":
            prog = getattr(strategy, "program", None)
            if prog is None or not prog.meta.get("quantized"):
                from repro.core import lower
                prog = lower.lower_strategy(g, strategy, qm)
            self.program = prog
        elif strategy is not None:
            # ref path: horizontal (shared-input) groups execute per-member —
            # the sharing is a LOAD-time optimization, numerics are identical
            from repro.core.pathsearch import order_groups
            groups = [list(grp) for grp in strategy.groups]
            groups += [[m] for hg in strategy.horizontal for m in hg]
            groups += [[h] for h in strategy.meta.get("host_nodes", [])]
            self.groups = order_groups(g, groups)
        else:
            self.groups = [[n] for n in g.compute_nodes()]
        self._fn = None
        self._fb_reasons = None
        # devices the outputs lived on, recorded on the first call of each
        # input shape (an executor's placement does not change after that)
        self.devices_seen: set = set()
        self._placed_shapes: set = set()
        self._in_shape = next((g.shape(n.name) for n in g if n.op == "input"),
                              None)

    def _validate_input(self, x) -> None:
        """Fail fast with a clear message instead of a deep Pallas/XLA shape
        error.  The graph's batch dimension is a planning default, not a
        constraint: any N >= 1 is accepted (dynamic batching stacks requests),
        while dtype, rank and the per-image extents must match the graph."""
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if dtype is None or jnp.dtype(dtype) != jnp.int8:
            raise ValueError(
                f"Int8Executor input must be int8 (quantize first, e.g. "
                f"quantize.quantize_to(x, qm.f_a[input])); got dtype {dtype}")
        if self._in_shape is None:
            return
        if shape is None or len(shape) != 4:
            raise ValueError(
                f"Int8Executor input must be rank-4 NHWC; got shape {shape}")
        if tuple(shape[1:]) != tuple(self._in_shape[1:]):
            raise ValueError(
                f"Int8Executor input spatial/channel extents {tuple(shape[1:])} "
                f"do not match the compiled graph's {tuple(self._in_shape[1:])} "
                f"(any batch size is accepted; H/W/C are fixed at compile time)")
        if shape[0] < 1:
            raise ValueError("Int8Executor input batch must be >= 1")

    def _build(self):
        g, qm = self.g, self.qm
        outputs = [n.name for n in g if not g.consumers(n.name)]

        if self.backend == "pallas":
            from repro.core.lower import FusedLaunch
            from repro.kernels.conv_fused import ops as fused_ops
            items = list(self.program.items)

            def fn(x):
                env = {}
                for node in g:
                    if node.op == "input":
                        env[node.name] = x
                for item in items:
                    if isinstance(item, FusedLaunch):
                        env.update(fused_ops.run_launch(item, env, qm))
                    else:
                        for name in item.nodes:
                            env[name] = _int8_node(g, g.nodes[name], env, qm)
                return {o: env[o] for o in outputs}
        else:
            def fn(x):
                env = {}
                for node in g:
                    if node.op == "input":
                        env[node.name] = x
                for group in self.groups:
                    for name in group:
                        env[name] = _int8_node(g, g.nodes[name], env, qm)
                return {o: env[o] for o in outputs}

        return jax.jit(fn)

    def __call__(self, x) -> dict:
        """Run one batch and return its outputs on the host.  ``x`` is an
        int8 batch, or a :class:`Dispatched` one whose outputs this waits
        for (no second run)."""
        out = x.out if isinstance(x, Dispatched) else self.dispatch(x)
        return {k: np.asarray(v) for k, v in out.items()}

    def dispatch(self, x: np.ndarray) -> dict:
        """Copy one batch in and enqueue the program; returns its outputs as
        device arrays without waiting for the device (reading one, e.g. with
        ``np.asarray``, waits for it).  The same jitted program as
        ``__call__``: no other compile."""
        from repro.obs.metrics import REGISTRY

        self._validate_input(x)
        if self._fn is None:
            self._fn = self._build()
        out = self._fn(jnp.asarray(x))
        if x.shape not in self._placed_shapes:
            self._placed_shapes.add(x.shape)
            for v in out.values():
                self.devices_seen.update(v.devices())
            if self.program is not None:
                self._export_program_stats(x.shape[0])
        REGISTRY.counter("executor.calls").inc()
        if self.program is not None:
            # the jitted program dispatches every item per call; meta carries
            # the per-call split the lowering decided on
            REGISTRY.counter("executor.fused_launches").inc(
                self.program.meta.get("n_launches", 0))
            REGISTRY.counter("executor.fallback_launches").inc(
                self.program.meta.get("n_fallbacks", 0))
            # per-reason fallback counters: the lowering records a machine-
            # readable reason on every RefFallback (lower.FALLBACK_REASONS);
            # exporting it labelled makes a lowering-gap regression (a YOLO op
            # sliding back to the reference path) visible on /metrics instead
            # of only moving an aggregate
            for reason, n in self._fallback_reasons().items():
                REGISTRY.counter("executor.fallback",
                                 {"reason": reason}).inc(n)
        return out

    def _export_program_stats(self, batch: int) -> None:
        """Gauges of what the program's fused launches occupy and execute
        (``lower.program_stats``), set on the first dispatch of each batch
        size: the largest launch's VMEM bytes, conv MACs per image as
        executed and as the model has them, the chain launches whose first
        conv's taps are folded into the contraction, and the weight bytes
        one batch pulls from HBM, labelled by the batch size."""
        from repro.core.lower import program_stats
        from repro.obs.metrics import REGISTRY

        st = program_stats(self.g, self.program, batch)
        for name in ("kernel_vmem_bytes_max", "conv_macs_executed",
                     "conv_macs_model", "taps_folded_launches"):
            REGISTRY.gauge(f"executor.{name}").set(st[name])
        REGISTRY.gauge("executor.weight_bytes_fetched",
                       {"batch": batch}).set(st["weight_bytes_fetched"])

    def _fallback_reasons(self) -> dict:
        """reason -> launches-per-call, computed once from the program."""
        if self._fb_reasons is None:
            from collections import Counter as _Counter
            self._fb_reasons = dict(_Counter(
                fb.reason for fb in self.program.fallbacks()))
        return self._fb_reasons


# Defined after Int8Executor on purpose: the compiled kernels carry the
# source lines of the executor's traced program and the persistent compile
# cache keys on them, so lines added above it change every model's key.
class Dispatched:
    """A batch that :meth:`Int8Executor.dispatch` enqueued: its input and
    its outputs, still on the device.  Calling the executor on it waits for
    the outputs and returns them on the host, so the executor's call stays
    the one place where the program's answers reach the host."""

    def __init__(self, x, out: dict):
        self.x = x                      # the batch as dispatched
        self.out = out                  # output name -> device array

    def __len__(self) -> int:
        return len(self.x)


def build_group_callable(g: XGraph, group: list, params_or_qm):
    """One group as a standalone jitted callable with random inputs — the
    'on-board' evaluator's unit of measurement."""
    in_names = list(dict.fromkeys(
        i for nm in group for i in g.nodes[nm].inputs
        if i not in group))
    rng = np.random.default_rng(0)

    if isinstance(params_or_qm, QuantizedModel):
        qm = params_or_qm
        # full-range int8 activations: measuring on standard-normal data cast
        # to int truncates to {-2..2}, which makes on-board timings run on
        # near-all-zero tensors (and constant-folds away saturation work)
        ins = [jnp.asarray(rng.integers(-128, 128, g.shape(i)), jnp.int8)
               for i in in_names]

        @jax.jit
        def fn(*xs):
            env = dict(zip(in_names, xs))
            for nm in group:
                env[nm] = _int8_node(g, g.nodes[nm], env, qm)
            return env[group[-1]]
    else:
        params = params_or_qm
        ins = [jnp.asarray(rng.standard_normal(g.shape(i)), jnp.float32)
               for i in in_names]

        @jax.jit
        def fn(*xs):
            env = dict(zip(in_names, xs))
            for nm in group:
                env[nm] = _float_node(g, g.nodes[nm], env, params)
            return env[group[-1]]

    return fn, ins
