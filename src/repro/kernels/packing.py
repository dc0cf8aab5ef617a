"""Bit-packed plan-component slabs: sub-int32 table codes in int32 words.

The engine's plan components are small non-negative (or small-magnitude)
integers — ``t_ust`` values are at most ``w_out`` bits, ``t_idx`` indexes a
handful of subtables, ``t_rsh``/``t_lb`` are tiny shift amounts / low-bit
codes, ``t_bias`` is a small signed correction — yet the device slabs store
every element as a full int32 lane (`kernels/ops.py` pads each component to
int32).  That 2–16x of dead weight is exactly the footprint the paper's
compression wins back, so the serving hot path packs each component into
the narrowest sufficient width: codes are biased by the component minimum
(so signed biases pack losslessly), ``per_word = 32 // width`` codes share
one int32 word, and the kernels unpack with one extra take + shift + mask
(:func:`unpack_take` — shift/mask statics for the per-site kernels, traced
metas for the multi-site single-grid kernel).

Every in-kernel table read goes through :func:`lane_take`, the one
component lookup Mosaic lowers: tables are laid out as ``(R, 128)`` lane
rows (:func:`lane_rows`) and read with a per-row lane gather, because the
TPU lowering accepts only same-shape 2-D gathers (a 1-D ``jnp.take`` on a
VMEM table is refused with ``Only 2D gather is supported``).

Packing is **lossless by construction** and round-trip asserted
(``unpack_array(*pack_array(a)) == a``, hypothesis-tested for widths 2–16
in tests/test_kernels_fused.py); the gather backend and every
serialization path keep consuming the unpacked int32 arrays untouched.
Widths above :data:`MAX_PACK_WIDTH` fall back to raw int32 storage
(``width=32``, one code per word) so pathological tables never lose bits.
"""
from __future__ import annotations

import numpy as np

# Canonical component order of a decomposed plan's device arrays.  The
# packed meta tables of the multi-site kernel index components by this
# order, so it is part of the slab format.
COMPONENTS = ("t_ust", "t_idx", "t_rsh", "t_bias", "t_lb")

# Widest width still packed (>= 2 codes per int32 word); anything wider
# stores raw.  Plan components are bounded by w_out <= 16 bits in
# practice, so the fallback is a safety valve, not a real path.
MAX_PACK_WIDTH = 16

# Lane width of a TPU vector register: the table row length lane_take reads.
LANES = 128


def needed_width(a: np.ndarray) -> tuple[int, int]:
    """(width, offset) of the narrowest biased encoding of ``a``.

    ``offset`` is the component minimum (biasing makes signed biases
    non-negative); ``width`` the bit count of the biased maximum, at
    least 1 so empty/constant components stay representable.
    """
    a = np.asarray(a)
    if a.size == 0:
        return 1, 0
    offset = int(a.min())
    span = int(a.max()) - offset
    return max(1, int(span).bit_length()), offset


def pack_array(a: np.ndarray, width: int | None = None,
               offset: int | None = None) -> tuple[np.ndarray, dict]:
    """Pack int array ``a`` (1-D or 2-D, packed along the last axis) into
    int32 words.  Returns ``(words, meta)`` with ``meta`` the python-int
    unpack parameters ``{"width", "offset", "per_word", "n"}``.
    """
    a = np.asarray(a, np.int64)
    if width is None or offset is None:
        width, offset = needed_width(a)
    if width > MAX_PACK_WIDTH:
        width, offset = 32, 0
    per_word = 32 // width
    n = a.shape[-1]
    meta = {"width": width, "offset": offset, "per_word": per_word, "n": n}
    if width == 32:
        return a.astype(np.int32), meta
    codes = (a - offset).astype(np.uint64)
    if codes.size and int(codes.max()) >> width:
        raise ValueError(
            f"pack_array: value {int(a.max())} does not fit width {width} "
            f"at offset {offset}")
    n_words = -(-n // per_word)
    pad = n_words * per_word - n
    if pad:
        pad_shape = a.shape[:-1] + (pad,)
        codes = np.concatenate(
            [codes, np.zeros(pad_shape, np.uint64)], axis=-1)
    codes = codes.reshape(a.shape[:-1] + (n_words, per_word))
    shifts = (np.arange(per_word, dtype=np.uint64) * width)
    words = (codes << shifts).sum(axis=-1, dtype=np.uint64)
    return words.astype(np.uint32).view(np.int32), meta


def unpack_array(words: np.ndarray, meta: dict) -> np.ndarray:
    """Exact inverse of :func:`pack_array` (host side, numpy int32)."""
    width, offset = meta["width"], meta["offset"]
    per_word, n = meta["per_word"], meta["n"]
    words = np.asarray(words)
    if width == 32:
        return words[..., :n].astype(np.int32)
    w = words.view(np.uint32).astype(np.uint64)
    shifts = (np.arange(per_word, dtype=np.uint64) * width)
    codes = (w[..., None] >> shifts) & ((1 << width) - 1)
    flat = codes.reshape(words.shape[:-1] + (-1,))[..., :n]
    return (flat.astype(np.int64) + offset).astype(np.int32)


def lane_rows(a):
    """Lay a table out as lane rows: ``(..., n) -> (..., ceil(n/128), 128)``,
    zero-padding the tail.  Free (a reshape) when ``n`` is already a
    multiple of 128, as the raw int32 slabs are."""
    import jax.numpy as jnp

    n = a.shape[-1]
    pad = (-n) % LANES
    if pad:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
    return a.reshape(a.shape[:-1] + (-1, LANES))


def lane_take(table, idx):
    """Exact gather ``table.reshape(-1)[idx]`` from a lane-row table.

    ``table`` is ``(R, 128)`` (:func:`lane_rows`), ``idx`` a 2-D int32
    array of in-range, non-negative flat indices.  Each 128-lane column
    chunk of ``idx`` gathers its lane from every table row broadcast to
    the chunk's shape (``take_along_axis`` on the lane axis, which Mosaic
    lowers to one dynamic gather per vreg) and keeps the row ``idx >> 7``
    selects — ``R`` is at most 8 for a 10-bit table.  Pure integer
    selection, so bit-identical to ``jnp.take`` on the flat table.
    """
    import jax.numpy as jnp

    rows, cols = idx.shape
    if rows == 1:
        # the Mosaic gather lowering needs at least two sublanes
        return lane_take(table, jnp.broadcast_to(idx, (2, cols)))[:1]
    hi = jnp.right_shift(idx, 7)
    lo = idx & (LANES - 1)
    outs = []
    for c0 in range(0, cols, LANES):
        lo_c, hi_c = lo[:, c0:c0 + LANES], hi[:, c0:c0 + LANES]
        acc = None
        for r in range(table.shape[0]):
            row = jnp.broadcast_to(table[r:r + 1], (rows, LANES))
            got = jnp.take_along_axis(row, lo_c, axis=1)
            acc = got if acc is None else jnp.where(hi_c == r, got, acc)
        outs.append(acc)
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def unpack_take(words, idx, *, width: int, offset: int, per_word: int):
    """Gather element ``idx`` out of a packed lane-row word table — the
    in-kernel unpack with **static** shift/mask parameters (the per-site
    kernels).

    ``(word >> shift) & mask`` is correct under arithmetic right shift:
    the mask discards any sign-extension bits, so the extracted field
    equals the stored biased code regardless of the word's sign.
    """
    if width == 32:
        return lane_take(words, idx)
    w = lane_take(words, idx // per_word)
    sh = (idx % per_word) * width
    return ((w >> sh) & ((1 << width) - 1)) + offset


def unpack_take_traced(words, idx, width, offset, per_word):
    """Traced-meta variant of :func:`unpack_take` for the multi-site
    kernel, where width/offset/per_word are int32 values read from the
    per-(site, component) meta side table.  Widths are <= 16 by the
    multi-site builder's contract (raw-int32 fallback is rejected there),
    so the mask ``(1 << width) - 1`` never overflows int32.
    """
    import jax.numpy as jnp

    w = lane_take(words, idx // per_word)
    sh = (idx % per_word) * width
    mask = jnp.left_shift(jnp.int32(1), width) - 1
    return (jnp.right_shift(w, sh) & mask) + offset


def pack_component_dict(arrays: dict) -> tuple[dict, dict]:
    """Pack every plan component of an ``arrays`` dict (values indexable
    as numpy; 1-D per-plan or 2-D stacked ``(L, n)``).  Returns
    ``(packed_arrays, pack_meta)`` keyed by component name."""
    packed, meta = {}, {}
    for c, a in arrays.items():
        packed[c], meta[c] = pack_array(np.asarray(a))
    return packed, meta


def packed_nbytes(packed: dict) -> int:
    """Device bytes of a packed component dict."""
    return sum(int(np.asarray(a).size) * 4 for a in packed.values())
