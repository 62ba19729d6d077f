"""Pallas TPU kernel: fused int8 op-chain programs.

This is the paper's fused-operation executed as ONE on-chip program — the
LOAD/CONV/POOL/MISC/SAVE pipeline of Fig. 8/9 mapped to the TPU, generalized
from single conv(+tail) patterns to whole lowered *chains*
(``lower.FusedLaunch.stages``):

* LOAD  -> Pallas grid DMA: BlockSpecs stage the padded input image, each
           stage's weight panel and bias slice, and any eltwise side inputs
           into VMEM (double-buffered across grid steps by the Pallas
           pipeline);
* CONV  -> MXU matmuls: every conv stage is computed as kh*kw shifted
           patch-matmuls accumulated in int32 — intermediate feature maps of
           the chain stay resident in VMEM and NEVER touch HBM;
* MISC  -> requantize (+ReLU), eltwise-add on a DMA'd side input, and
           max/avg/global pooling run on the VPU over the resident tile;
* SAVE  -> the output BlockSpec writes the finished int8 tile back.

Coordinate convention (how padding/ceil semantics stay bit-exact): every
tensor of the chain lives in *padded coordinates*.  Walking backward from the
final output (offset 0), each stage with stride ``s`` and pad ``p`` maps its
output offset ``Q`` to an input offset ``Q*s + p``; the external image is
physically pre-padded by the accumulated offset (with the first stage's pad
identity), and after each stage the kernel masks rows/cols falling outside
the stage's true extent to the *consumer's* pad identity (0 for conv/eltwise/
avg-sum, -128 for maxpool).  That reproduces exactly the reference semantics
of zero-padded conv, -128-padded (and ceil-extended) maxpool, and zero-padded
(and ceil-extended, count-include-pad) avgpool from ``int8_ops``.

Tile shape: the grid is (batch, row tiles, width tiles, OC tiles) — all
three tile extents are compile-time decisions (``FusedLaunch.tile``, chosen
by the tile-shape search; kernel heuristics otherwise).  Width tiles read
halo-overlapped windows of the staged image (ref loads at 8-aligned offsets,
mirroring the row axis), and ragged bottom/right tiles compute into
padded-output slack that the launcher slices off — the same padded-
coordinate masking that handles ceil-mode pools keeps every valid position
bit-exact at interior tile boundaries.  The OC axis tiles the FINAL conv's
output channels (TOC); stages upstream of it compute full channels (a conv
consumer needs all of them), stages downstream are channelwise and ride the
TOC slice.

Numerics are EXACTLY ``int8_ops``: int32 accumulate, round-half-away shift,
saturate — validate.py enforces bit-equality.  The horizontal variant batches
sibling convs over OC-stacked weights with *per-channel* shift/ReLU vectors.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.hw.device import V5E_VMEM_BYTES

I8_MIN = -128


def _round_shift(x, s: int):
    if s == 0:
        return x
    if s < 0:
        return x << (-s)
    ax = jnp.abs(x)
    r = (ax + (1 << (s - 1))) >> s
    return jnp.sign(x) * r


def _round_shift_vec(x, s):
    """x (..., C) int32, s (C,) int32 per-channel shift (may be negative)."""
    s = s.reshape((1,) * (x.ndim - 1) + (-1,))
    sp = jnp.maximum(s, 1)
    right = jnp.sign(x) * ((jnp.abs(x) + (1 << (sp - 1))) >> sp)
    return jnp.where(s > 0, right, x << jnp.maximum(-s, 0))


def _sat8(x):
    return jnp.clip(x, -128, 127).astype(jnp.int8)


# ------------------------------------------------------------ static geometry
def _stage_geom(st):
    """(ekh, ekw, sh, sw, ph, pw) of one stage spec."""
    if st[0] == "conv":
        _, _, kh, kw, sh, sw, ph, pw, dh, dw = st[:10]
        return (dh * (kh - 1) + 1, dw * (kw - 1) + 1, sh, sw, ph, pw)
    if st[0] == "pool":
        _, _, _, kph, kpw, sph, spw, pph, ppw = st[:9]
        return (kph, kpw, sph, spw, pph, ppw)
    return (1, 1, 1, 1, 0, 0)   # elt


def _fill_of(st) -> int:
    """Pad identity a stage wants on its *input*."""
    return I8_MIN if (st[0] == "pool" and st[2] == "max") else 0


def chain_geometry(chain, th: int, oh: int, ow: int, tw: int | None = None
                   ) -> dict:
    """Static tile geometry of a lowered chain.

    Shared by the kernel body (trace-time python) and the launcher (physical
    padding); the two must agree or masking goes stale.  ``tw`` tiles the
    width axis (default: the full output width — the PR-4 single-column
    grid); neighbouring width tiles read halo-overlapped input regions, and
    ragged bottom/right tiles run on padded coordinates masked back to the
    true extents (``n_h``/``n_w`` are ceil-divided).
    """
    tw = ow if tw is None else tw
    m = len(chain)
    rows = [0] * m
    cols = [0] * m
    fout = [0] * m           # padded row-offset factor of stage i's output
    foutw = [0] * m          # padded col-offset factor of stage i's output
    q = [(0, 0)] * m         # padded-coordinate offset of stage i's output
    r, c, f, fw, qq = th, tw, th, tw, (0, 0)
    for i in range(m - 1, -1, -1):
        rows[i], cols[i], fout[i], foutw[i], q[i] = r, c, f, fw, qq
        ekh, ekw, sh, sw, ph, pw = _stage_geom(chain[i])
        r = (r - 1) * sh + ekh
        c = (c - 1) * sw + ekw
        f = f * sh
        fw = fw * sw
        qq = (qq[0] * sh + ph, qq[1] * sw + pw)
    n_h = -(-oh // th)
    n_w = -(-ow // tw)
    sides = []
    for i, st in enumerate(chain):
        if st[0] == "elt":
            q_in = q[i]      # elt: input coords == output coords
            sides.append({"q": q_in, "rows": rows[i], "cols": cols[i],
                          "h_req": (n_h - 1) * fout[i] + rows[i],
                          "w_req": (n_w - 1) * foutw[i] + cols[i],
                          "f": fout[i], "fw": foutw[i]})
    return {
        "in_rows": r, "in_cols": c, "f_in": f, "fw_in": fw, "q_in": qq,
        "h_req": (n_h - 1) * f + r, "w_req": (n_w - 1) * fw + c,
        "rows": rows, "cols": cols, "fout": fout, "foutw": foutw, "q": q,
        "fill0": _fill_of(chain[0]) if chain else 0,
        "sides": sides, "th": th, "tw": tw, "n_h": n_h, "n_w": n_w,
    }


# ------------------------------------------------------------------- kernels
def _ds(start, size: int, stride: int = 1):
    return pl.ds(start, size, stride) if stride > 1 else pl.ds(start, size)


def _col_start(jw, f: int, n_w: int):
    """Width offset of width tile ``jw``: static 0 for a single column, else
    ``jw * f`` (``f`` is a multiple of 8 once ``ops`` legalized the tile,
    which keeps the dynamic sublane offset aligned for Mosaic)."""
    if n_w == 1:
        return 0
    start = jw * f
    return pl.multiple_of(start, 8) if f % 8 == 0 else start


def _rows(n: int, body) -> None:
    """``body(r)`` for every output row r of a stage.  A loop, not an unroll:
    each iteration touches one (width, channels) row, which keeps the Mosaic
    program small and every value 2-D (no sublane-merging reshapes)."""
    def step(r, carry):
        body(r)
        return carry
    jax.lax.fori_loop(0, n, step, 0)


LANES = 128


def _groups(c: int) -> list:
    """(offset, width) of the 128-lane channel groups of a C-channel map.
    Stage buffers hold one group per leading index: Mosaic's strided load
    needs a minor dimension of at most 128 lanes."""
    return [(g, min(LANES, c - g)) for g in range(0, c, LANES)]


def _buf_shape(rows: int, cols: int, c: int) -> tuple:
    return (len(_groups(c)), rows, cols, min(c, LANES))


def _col_valid(shape, col0, q, true_w):
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + col0
    return (cols >= q) & (cols < q + true_w)


def _conv_acc(src, c_in, w_ref, r, out_c, kh, kw, sh, sw, dh, dw):
    """int32 accumulator of output row r of a conv read from the int32 VMEM
    buffer ``src``: kh*kw (strided) window loads per channel group, each a
    matmul against that group's weight rows.  The buffer holds saturated int8
    values, so int8 operands with int32 accumulation are exact (Mosaic has no
    int32 matmul)."""
    acc = jnp.zeros((out_c, w_ref.shape[-1]), jnp.int32)
    for i in range(kh):
        for j in range(kw):
            for g, (lo, cw) in enumerate(_groups(c_in)):
                patch = src[g, r * sh + i * dh, _ds(j * dw, out_c, sw), :cw]
                acc = acc + jnp.dot(patch.astype(jnp.int8),
                                    w_ref[i, j, lo:lo + cw],
                                    preferred_element_type=jnp.int32)
    return acc


def _conv_row(src, c_in, w_ref, b_ref, st, r, out_c):
    _, _, kh, kw, sh, sw, _, _, dh, dw, shift, relu = st[:12]
    acc = _conv_acc(src, c_in, w_ref, r, out_c, kh, kw, sh, sw, dh, dw)
    y = _round_shift(acc + b_ref[...], shift)
    if relu:
        y = jnp.maximum(y, 0)
    return jnp.clip(y, -128, 127)


def _pool_row(src, g, cw, st, r, out_c):
    _, _, pkind, kph, kpw, sph, spw = st[:7]
    s = None
    for i in range(kph):
        for j in range(kpw):
            win = src[g, r * sph + i, _ds(j, out_c, spw), :cw]
            if s is None:
                s = win
            elif pkind == "max":
                s = jnp.maximum(s, win)
            else:
                s = s + win
    return s if pkind == "max" else _avg(s, st[11])


def _avg(s, cnt: int):
    return jnp.sign(s) * ((jnp.abs(s) + cnt // 2) // cnt)


def _elt_apply(t, side, st):
    _, _, s_main, s_side, relu_out = st[:5]
    z = _round_shift(t, s_main) + _round_shift(side, s_side)
    if relu_out:
        z = jnp.maximum(z, 0)
    return jnp.clip(z, -128, 127)


def _true_hw(st) -> tuple:
    return ((st[12], st[13]) if st[0] == "conv" else
            (st[9], st[10]) if st[0] == "pool" else (st[5], st[6]))


def _chain_kernel(*refs, chain, geom, chans):
    """Every stage reads its input rows from an int32 VMEM buffer (strided
    windows are ref loads: Mosaic has no strided value slice and no strided
    int8 load) and writes its masked output rows into the next stage's
    buffer; the last stage writes the output block.  ``chans[i]`` is the
    channel count of stage i's input buffer."""
    n_conv = sum(1 for st in chain if st[0] == "conv")
    n_side = sum(1 for st in chain if st[0] == "elt")
    x_ref = refs[0]
    wrefs = refs[1:1 + 2 * n_conv]
    srefs = refs[1 + 2 * n_conv:1 + 2 * n_conv + n_side]
    o_ref = refs[1 + 2 * n_conv + n_side]
    bufs = refs[2 + 2 * n_conv + n_side:]
    j = pl.program_id(1)
    jw = pl.program_id(2)
    n_w = geom["n_w"]
    row_in = j * geom["f_in"]
    col_in = pl.ds(_col_start(jw, geom["fw_in"], n_w), geom["in_cols"])

    def stage_in(r):
        for g, (lo, cw) in enumerate(_groups(chans[0])):
            bufs[0][g, r, :, :cw] = x_ref[0, row_in + r, col_in,
                                          lo:lo + cw].astype(jnp.int32)
    _rows(geom["in_rows"], stage_in)

    wi = si = 0
    for i, st in enumerate(chain):
        out_r, out_c = geom["rows"][i], geom["cols"][i]
        last = i + 1 == len(chain)
        src, c_in = bufs[i], chans[i]
        if st[0] == "pool" and st[2] == "gap":
            # one output position: the whole staged map, summed row by row
            for g, (lo, cw) in enumerate(_groups(c_in)):
                tot = jnp.zeros((1, cw), jnp.int32)
                for r in range(src.shape[1]):
                    tot = tot + jnp.sum(src[g, r, :, :cw], axis=0,
                                        keepdims=True)
                o_ref[0, 0, :, lo:lo + cw] = _sat8(_avg(tot, st[11]))
            continue
        if st[0] == "conv":
            w_ref, b_ref = wrefs[2 * wi], wrefs[2 * wi + 1]
            wi += 1
        if st[0] == "elt":
            s_ref, s_row = srefs[si], j * geom["fout"][i]
            s_col = pl.ds(_col_start(jw, geom["foutw"][i], n_w), out_c)
            si += 1
        if not last:
            true_h, true_w = _true_hw(st)
            row0 = j * geom["fout"][i]
            q = geom["q"][i]
            fill = _fill_of(chain[i + 1])

        def emit(r, g, lo, cw, y):
            if last:
                o_ref[0, r, :, lo:lo + cw] = _sat8(y)
                return
            ok = _col_valid(y.shape, jw * geom["foutw"][i], q[1], true_w)
            row = row0 + r
            ok = ok & (row >= q[0]) & (row < q[0] + true_h)
            bufs[i + 1][g, r, :, :cw] = jnp.where(ok, y, fill)

        def body(r):
            if st[0] == "conv":
                y = _conv_row(src, c_in, w_ref, b_ref, st, r, out_c)
                for g, (lo, cw) in enumerate(_groups(y.shape[-1])):
                    emit(r, g, lo, cw, y[:, lo:lo + cw])
                return
            for g, (lo, cw) in enumerate(_groups(c_in)):
                if st[0] == "pool":
                    y = _pool_row(src, g, cw, st, r, out_c)
                else:
                    side = s_ref[0, s_row + r, s_col, lo:lo + cw]
                    y = _elt_apply(src[g, r, :, :cw],
                                   side.astype(jnp.int32), st)
                emit(r, g, lo, cw, y)
        _rows(out_r, body)


def _compiler_params(interpret: bool):
    """Scoped-VMEM limit of one launch: the budget ``hw.TPU_V5E`` plans tiles
    under (Mosaic's 16 MiB default is too small for the stem's whole-image
    block, ~15 MiB lane-padded and double-buffered).  The fusion search
    admits a chain only if its ``LaunchBlocks.vmem_bytes()`` fits it."""
    return (None if interpret
            else pltpu.CompilerParams(vmem_limit_bytes=V5E_VMEM_BYTES))


def _vmem(shape):
    return pltpu.VMEM(tuple(int(d) for d in shape), jnp.int32)


# ------------------------------------------------------------ VMEM footprint
SUBLANES = {1: 32, 4: 8}      # rows of one (sublane, lane) tile, by itemsize


def padded_bytes(shape, itemsize: int) -> int:
    """Bytes a VMEM buffer of ``shape`` occupies: Mosaic lays out its two
    minor dims in tiles of 128 lanes by 8 rows (int32) or 32 rows (int8)."""
    *lead, sub, lane = shape
    rows = SUBLANES[itemsize]
    return (math.prod(lead) * -(-sub // rows) * rows
            * -(-lane // LANES) * LANES * itemsize)


@dataclasses.dataclass(frozen=True)
class Block:
    """One pipelined operand of a launch: its block shape, element size, and
    for each block dim the grid axis its block index follows (None: 0)."""
    shape: tuple
    itemsize: int
    follows: tuple

    def index_map(self):
        follows = self.follows
        return lambda *ids: tuple(0 if a is None else ids[a] for a in follows)

    def fetches(self, grid) -> int:
        """Copies of this block from HBM over the grid: the pipeline fetches
        it again whenever its block index changes between consecutive
        (row-major) grid steps."""
        moving = [a for a in self.follows if a is not None and grid[a] > 1]
        return math.prod(grid[:max(moving) + 1]) if moving else 1


@dataclasses.dataclass(frozen=True)
class LaunchBlocks:
    """Grid, pipelined blocks (operands in argument order, then the output)
    and int32 scratch of one launch: what it allocates in VMEM and what it
    pulls from HBM."""
    grid: tuple
    inputs: tuple
    out: Block
    scratch: tuple
    params: tuple              # indices into ``inputs``: weights and biases
    chans: tuple = ()          # chain: channels of each stage's input buffer

    def vmem_bytes(self) -> int:
        """Every pipelined block double-buffered, plus the scratch."""
        piped = sum(padded_bytes(b.shape, b.itemsize)
                    for b in self.inputs + (self.out,))
        return 2 * piped + sum(padded_bytes(s, 4) for s in self.scratch)

    def param_bytes_fetched(self) -> int:
        """Weight and bias bytes the grid pulls from HBM."""
        return sum(math.prod(b.shape) * b.itemsize * b.fetches(self.grid)
                   for b in (self.inputs[i] for i in self.params))


def chain_blocks(chain, geom, x_shape, weight_shapes, side_shapes, *, toc,
                 oc) -> LaunchBlocks:
    """Blocks of a chain launch over the pre-padded input ``x_shape``, one
    (KH, KW, IC, OC) weight panel per conv stage and one pre-padded side per
    elt stage.  The final conv's weights, bias and downstream sides ride the
    OC-tile axis; every other conv's panel is resident whole."""
    n, hp, wp, c = x_shape
    conv_idx = [i for i, st in enumerate(chain) if st[0] == "conv"]
    last_conv = conv_idx[-1] if conv_idx else -1
    inputs = [Block((1, hp, wp, c), 1, (0, None, None, None))]
    for (kh, kw, ic, oc_i), ci in zip(weight_shapes, conv_idx):
        if ci == last_conv:
            inputs += [Block((kh, kw, ic, toc), 1, (None, None, None, 3)),
                       Block((1, toc), 4, (None, 3))]
        else:
            inputs += [Block((kh, kw, ic, oc_i), 1, (None,) * 4),
                       Block((1, oc_i), 4, (None, None))]
    params = tuple(range(1, len(inputs)))
    elt_idx = [i for i, st in enumerate(chain) if st[0] == "elt"]
    for ei, (_, shp, swp, sc) in zip(elt_idx, side_shapes):
        if ei > last_conv:   # rides the TOC slice of the final conv
            inputs.append(Block((1, shp, swp, toc), 1, (0, None, None, 3)))
        else:
            inputs.append(Block((1, shp, swp, sc), 1, (0, None, None, None)))

    # one int32 buffer per stage input: the staged image window, then every
    # intermediate map (channels: a conv's panel width — TOC for the final
    # conv — else the channels it was fed)
    chans, wi = [c], 0
    for i, st in enumerate(chain[:-1]):
        ch = chans[-1]
        if st[0] == "conv":
            ch = toc if i == last_conv else int(weight_shapes[wi][-1])
            wi += 1
        chans.append(ch)
    extents = [(geom["in_rows"], geom["in_cols"])] + [
        (geom["rows"][i], geom["cols"][i]) for i in range(len(chain) - 1)]
    return LaunchBlocks(
        grid=(n, geom["n_h"], geom["n_w"], oc // toc), inputs=tuple(inputs),
        out=Block((1, geom["th"], geom["tw"], toc), 1, (0, 1, 2, 3)),
        scratch=tuple(_buf_shape(r, cc, ch)
                      for (r, cc), ch in zip(extents, chans)),
        params=params, chans=tuple(chans))


def horizontal_blocks(x_shape, w_shape, *, stride, th, tw, toc, oh, ow
                      ) -> LaunchBlocks:
    """Blocks of a horizontal launch: the pre-padded input, OC-stacked
    weights and the bias, shift and ReLU vectors, all but the input riding
    the OC-tile axis."""
    n, hp, wp, ic = x_shape
    kh, kw, _, oc = w_shape
    sh, sw = stride
    vec = Block((1, toc), 4, (None, 3))
    return LaunchBlocks(
        grid=(n, -(-oh // th), -(-ow // tw), oc // toc),
        inputs=(Block((1, hp, wp, ic), 1, (0, None, None, None)),
                Block((kh, kw, ic, toc), 1, (None, None, None, 3)),
                vec, vec, vec),
        out=Block((1, th, tw, toc), 1, (0, 1, 2, 3)),
        scratch=(_buf_shape((th - 1) * sh + kh, (tw - 1) * sw + kw, ic),),
        params=(1, 2))


def _pallas(kern, blocks: LaunchBlocks, out_shape, interpret: bool):
    return pl.pallas_call(
        kern,
        grid=blocks.grid,
        in_specs=[pl.BlockSpec(b.shape, b.index_map()) for b in blocks.inputs],
        out_specs=pl.BlockSpec(blocks.out.shape, blocks.out.index_map()),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.int8),
        scratch_shapes=[_vmem(s) for s in blocks.scratch],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )


def fused_chain_pallas(x_pad, weights, biases, sides, *, chain, th, toc,
                       oh, ow, oc, tw=None, interpret=False):
    """Launch a lowered chain as one kernel.

    x_pad:   (N, Hp, Wp, C) int8, pre-padded per ``chain_geometry`` with the
             first stage's pad identity.
    weights: one (KH, KW, IC, OC) int8 panel per conv stage, in chain order.
    biases:  one (1, OC) int32 row per conv stage.
    sides:   one pre-padded (N, sHp, sWp, OCs) int8 per elt stage.
    chain:   static stage specs (see ``core.lower``).
    tw:      width-tile size (default: full width).  Ragged bottom/right
             tiles compute into a padded output that is sliced back to
             (oh, ow) here — intermediate masking keeps every valid position
             bit-exact, the sliced-off slack is never read.
    """
    n = x_pad.shape[0]
    geom = chain_geometry(chain, th, oh, ow, tw)
    blocks = chain_blocks(chain, geom, x_pad.shape, [w.shape for w in weights],
                          [s.shape for s in sides], toc=toc, oc=oc)
    kern = functools.partial(_chain_kernel, chain=chain, geom=geom,
                             chans=blocks.chans)
    fn = _pallas(kern, blocks, (n, geom["n_h"] * th, geom["n_w"] * geom["tw"],
                                oc), interpret)
    args = [x_pad]
    for w, b in zip(weights, biases):
        args.extend([w, b])
    return fn(*args, *sides)[:, :oh, :ow]


# ------------------------------------------------------ horizontal (stacked)
def _horizontal_kernel(x_ref, w_ref, b_ref, s_ref, r_ref, o_ref, buf, *,
                       kh, kw, sh, sw, th, tw, n_w, ic):
    j = pl.program_id(1)
    jw = pl.program_id(2)
    in_rows, in_cols = buf.shape[1:3]
    row_in = j * th * sh
    col_in = pl.ds(_col_start(jw, tw * sw, n_w), in_cols)

    def stage_in(r):
        for g, (lo, cw) in enumerate(_groups(ic)):
            buf[g, r, :, :cw] = x_ref[0, row_in + r, col_in,
                                      lo:lo + cw].astype(jnp.int32)
    _rows(in_rows, stage_in)

    def body(r):
        acc = _conv_acc(buf, ic, w_ref, r, tw, kh, kw, sh, sw, 1, 1)
        y = _round_shift_vec(acc + b_ref[...], s_ref[...])
        y = jnp.where(r_ref[...] != 0, jnp.maximum(y, 0), y)
        o_ref[0, r] = _sat8(y)
    _rows(th, body)


def fused_horizontal_pallas(x_pad, w, b, shift_vec, relu_vec, *, stride,
                            th, toc, oh, ow, tw=None, interpret=False):
    """Sibling convs batched over OC-stacked weights.

    w: (KH, KW, IC, sum_OC) int8 stacked along OC; b, shift_vec, relu_vec:
    (1, sum_OC) int32 bias, per-channel requantization shift and ReLU mask.
    x_pad pre-padded (the launcher pads enough physical slack for the ragged
    bottom/right tiles; their zero-fed slack positions are sliced off here).
    """
    n, hp, wp, ic = x_pad.shape
    kh, kw = w.shape[:2]
    tw = ow if tw is None else tw
    blocks = horizontal_blocks(x_pad.shape, w.shape, stride=stride, th=th,
                               tw=tw, toc=toc, oh=oh, ow=ow)
    _, n_h, n_w, _ = blocks.grid
    kern = functools.partial(_horizontal_kernel, kh=kh, kw=kw, sh=stride[0],
                             sw=stride[1], th=th, tw=tw, n_w=n_w, ic=ic)
    fn = _pallas(kern, blocks, (n, n_h * th, n_w * tw, w.shape[-1]),
                 interpret)
    return fn(x_pad, w, b, shift_vec, relu_vec)[:, :oh, :ow]
