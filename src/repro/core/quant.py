"""Blockwise quantization (int8 / int4 / NF4) — the paper's §III-C substrate.

TPU adaptation (see DESIGN.md §5): blocks run along the *contraction*
dimension of each weight in multiples of 128 so the Pallas ``quant_matmul``
kernel can dequantize one (block × tile) at a time in VMEM and feed the MXU.
int4/NF4 values are packed two-per-uint8, so ``memory_analysis`` of the
dry-run reflects the true 4-bit footprint.

Layout for a weight of shape (..., K, N) with block B along K:
  q      : (..., G, B, N) int8      [8-bit]        G = K // B
           (..., G, B//2, N) uint8  [4-bit packed]
  scales : (..., G, 1, N) float32   absmax / levels

``quantize_tree`` applies this to every ≥2-D leaf of a param tree
(1-D leaves — norms, biases — stay in full precision, as in QLoRA).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# NF4 codebook (QLoRA, Dettmers et al. 2023) — quantiles of N(0,1), ±1 ends.
NF4_CODE = np.array([
    -1.0, -0.6961928009986877, -0.5250730514526367, -0.39491748809814453,
    -0.28444138169288635, -0.18477343022823334, -0.09105003625154495, 0.0,
    0.07958029955625534, 0.16093020141124725, 0.24611230194568634,
    0.33791524171829224, 0.44070982933044434, 0.5626170039176941,
    0.7229568362236023, 1.0], dtype=np.float32)


@partial(jax.tree_util.register_dataclass,
         data_fields=["q", "scales"],
         meta_fields=["bits", "mode", "block", "out_dtype", "orig_shape"])
@dataclasses.dataclass
class QTensor:
    q: jax.Array
    scales: jax.Array
    bits: int
    mode: str           # "linear" | "nf4"
    block: int
    out_dtype: Any
    orig_shape: tuple

    @property
    def shape(self):
        return self.orig_shape

    @property
    def ndim(self):
        return len(self.orig_shape)

    def nbytes_packed(self) -> int:
        return int(np.prod(self.q.shape)) * self.q.dtype.itemsize + \
            int(np.prod(self.scales.shape)) * self.scales.dtype.itemsize


def _blocked(x: jax.Array, block: int):
    *lead, K, N = x.shape
    block = min(block, K)
    assert K % block == 0, f"contraction dim {K} not divisible by block {block}"
    return x.reshape(*lead, K // block, block, N), block


def pack4(q: jax.Array) -> jax.Array:
    """Pack int4 values in [-8, 7] two-per-uint8 along axis -2: packed
    row i holds row i in the high nibble and row i + B/2 in the low one,
    so packing and unpacking are contiguous half-slices (no strided or
    interleaving relayout in the TPU kernels)."""
    u = (q + 8).astype(jnp.uint8)
    half = u.shape[-2] // 2
    return (u[..., :half, :] << 4) | u[..., half:, :]


def unpack4(p: jax.Array) -> jax.Array:
    hi = (p >> 4).astype(jnp.int8) - 8
    lo = (p & 0xF).astype(jnp.int8) - 8
    return jnp.concatenate([hi, lo], axis=-2)


def quantize(x: jax.Array, *, bits: int = 4, block: int = 128,
             mode: str = "linear") -> QTensor:
    orig_shape = tuple(x.shape)
    out_dtype = x.dtype
    xb, block = _blocked(x.astype(jnp.float32), block)
    absmax = jnp.max(jnp.abs(xb), axis=-2, keepdims=True)
    absmax = jnp.maximum(absmax, 1e-12)
    if mode == "nf4":
        assert bits == 4, "nf4 is a 4-bit codebook"
        scales = absmax
        normed = xb / scales                               # [-1, 1]
        code = jnp.asarray(NF4_CODE)
        idx = jnp.argmin(
            jnp.abs(normed[..., None] - code), axis=-1).astype(jnp.int8) - 8
        q = pack4(idx)
    elif bits == 8:
        scales = absmax / 127.0
        q = jnp.clip(jnp.round(xb / scales), -127, 127).astype(jnp.int8)
    elif bits == 4:
        scales = absmax / 7.0
        q = jnp.clip(jnp.round(xb / scales), -8, 7).astype(jnp.int8)
        q = pack4(q)
    else:
        raise ValueError(f"unsupported bits={bits}")
    return QTensor(q=q, scales=scales, bits=bits, mode=mode, block=block,
                   out_dtype=out_dtype, orig_shape=orig_shape)


def nf4_lookup(idx):
    """NF4 codebook value of each 4-bit code (0..15), as a select tree on
    the code's bits: element-wise work, where a table gather runs as a
    scalar gather on a TPU (and Mosaic lowers no general gather)."""
    vals = [float(c) for c in NF4_CODE]
    for bit in range(4):
        hi = ((idx >> bit) & 1) == 1
        vals = [jnp.where(hi, vals[2 * i + 1], vals[2 * i])
                for i in range(len(vals) // 2)]
    return vals[0]


def dequantize(qt: QTensor, dtype=None) -> jax.Array:
    dtype = dtype or qt.out_dtype
    if qt.bits == 4:
        vals = unpack4(qt.q)
        if qt.mode == "nf4":
            vals = nf4_lookup((vals + 8).astype(jnp.int32))
        else:
            vals = vals.astype(jnp.float32)
    else:
        vals = qt.q.astype(jnp.float32)
    x = vals * qt.scales
    # Shape is derived from the live arrays (not the static orig_shape) so
    # that sliced / lax.scan-consumed / all-gathered QTensors dequantize
    # correctly; orig_shape is metadata for the unsliced tensor only.
    *lead, G, B, N = x.shape
    return x.reshape(*lead, G * B, N).astype(dtype)


def maybe_dequantize(w, dtype=None):
    return dequantize(w, dtype) if isinstance(w, QTensor) else w


# param-name fragments never quantized (QLoRA keeps these full-precision)
DEFAULT_SKIP = ("router", "conv", "dt_bias", "a_log", "d_skip", "lam",
                "ln", "norm", "embed", "pos", "head", "bias", "lora",
                "slot", "w_rg", "w_ig")


def _quantizable(path: str, shape, dtype, min_size: int,
                 skip_names=DEFAULT_SKIP) -> bool:
    if any(s in path.lower() for s in skip_names):
        return False
    if len(shape) < 2 or int(np.prod(shape)) < min_size:
        return False
    if not jnp.issubdtype(dtype, jnp.floating):
        return False
    return True


def _pick_block(K: int, block: int) -> int:
    b = min(block, K)
    while K % b:
        b //= 2
    return max(b, 1)


def quantize_tree(params, *, bits: int, block: int = 128,
                  mode: str = "linear", min_size: int = 4096,
                  skip_names=DEFAULT_SKIP):
    """Quantize every eligible ≥2-D leaf (QLoRA keeps norms/biases/
    routers/convs/embeddings in full precision — filtered by name)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda l: isinstance(l, QTensor))
    out = []
    for path, leaf in flat:
        pstr = "/".join(str(k) for k in path)
        if isinstance(leaf, QTensor) or not _quantizable(
                pstr, leaf.shape, leaf.dtype, min_size, skip_names):
            out.append(leaf)
            continue
        b = _pick_block(leaf.shape[-2], block)
        eff_bits, eff_mode = bits, mode
        if b % 2:
            eff_bits, eff_mode = 8, "linear"  # can't pack odd blocks
        out.append(quantize(leaf, bits=eff_bits, block=b, mode=eff_mode))
    return jax.tree_util.tree_unflatten(treedef, out)


def qtensor_specs(shape, dtype, *, bits: int, block: int = 128,
                  mode: str = "linear") -> QTensor:
    """Abstract (ShapeDtypeStruct) QTensor matching ``quantize`` output."""
    *lead, K, N = shape
    b = _pick_block(K, block)
    if b % 2:
        bits, mode = 8, "linear"
    G = K // b
    if bits == 4:
        q = jax.ShapeDtypeStruct((*lead, G, b // 2, N), jnp.uint8)
    else:
        q = jax.ShapeDtypeStruct((*lead, G, b, N), jnp.int8)
    scales = jax.ShapeDtypeStruct((*lead, G, 1, N), jnp.float32)
    return QTensor(q=q, scales=scales, bits=bits, mode=mode, block=b,
                   out_dtype=dtype, orig_shape=tuple(shape))


def quantize_tree_specs(specs, *, bits: int, block: int = 128,
                        mode: str = "linear", min_size: int = 4096,
                        skip_names=DEFAULT_SKIP):
    """ShapeDtypeStruct analogue of ``quantize_tree`` (dry-run params)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda l: isinstance(
            l, (QTensor, jax.ShapeDtypeStruct)))
    out = []
    for path, leaf in flat:
        pstr = "/".join(str(k) for k in path)
        if isinstance(leaf, jax.ShapeDtypeStruct) and _quantizable(
                pstr, leaf.shape, leaf.dtype, min_size, skip_names):
            out.append(qtensor_specs(leaf.shape, leaf.dtype, bits=bits,
                                     block=block, mode=mode))
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def dequantize_tree(params, dtype=None):
    return jax.tree.map(
        lambda l: dequantize(l, dtype) if isinstance(l, QTensor) else l,
        params, is_leaf=lambda l: isinstance(l, QTensor))


def double_quantize(qt: QTensor, *, block: int = 256):
    """QLoRA double quantization: the f32 absmax scales are themselves
    int8-quantized (mean-offset absmax over flat blocks of ``block``),
    cutting per-block overhead from 32 to ~8.25 bits. Returns a plain
    dict (storage/communication container)."""
    s = qt.scales.astype(jnp.float32)
    flat = s.reshape(-1)
    pad = (-flat.size) % block
    flat = jnp.pad(flat, (0, pad))
    g = flat.reshape(-1, block)
    mean = g.mean(axis=1, keepdims=True)
    c = g - mean
    smax = jnp.maximum(jnp.abs(c).max(axis=1, keepdims=True), 1e-12) / 127.
    q = jnp.clip(jnp.round(c / smax), -127, 127).astype(jnp.int8)
    return {"q": qt.q, "s_q": q, "s_scale": smax[:, 0], "s_mean": mean[:, 0],
            "meta": dict(bits=qt.bits, mode=qt.mode, block=qt.block,
                         out_dtype=np.dtype(qt.out_dtype).name,
                         orig_shape=tuple(qt.orig_shape),
                         scales_shape=tuple(qt.scales.shape),
                         dq_block=block)}


def double_dequantize(dq: dict) -> QTensor:
    m = dq["meta"]
    flat = (dq["s_q"].astype(jnp.float32) * dq["s_scale"][:, None] +
            dq["s_mean"][:, None]).reshape(-1)
    n = int(np.prod(m["scales_shape"]))
    scales = flat[:n].reshape(m["scales_shape"])
    return QTensor(q=dq["q"], scales=scales, bits=m["bits"],
                   mode=m["mode"], block=m["block"],
                   out_dtype=np.dtype(m["out_dtype"]),
                   orig_shape=tuple(m["orig_shape"]))


def double_quant_bytes(dq: dict) -> int:
    b = dq["q"].size * dq["q"].dtype.itemsize
    b += dq["s_q"].size + dq["s_scale"].size * 4 + dq["s_mean"].size * 4
    return int(b)


def tree_bytes(params) -> int:
    """True communicated/stored bytes of a (possibly quantized) tree."""
    total = 0
    for leaf in jax.tree.leaves(
            params, is_leaf=lambda l: isinstance(l, QTensor)):
        if isinstance(leaf, QTensor):
            total += leaf.nbytes_packed()
        else:
            total += leaf.size * leaf.dtype.itemsize
    return int(total)
