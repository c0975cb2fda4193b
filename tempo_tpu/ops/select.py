"""Top-k result selection: pick the `limit` newest matching traces
WITHOUT shipping full masks to host.

The device filter produces (trace_mask, span_count) sized to the trace
axis. Materializing results used to mean one device->host transfer per
array plus a Python loop over every candidate -- on a high-latency
host<->device link each sync costs tens of ms, and the loop cost scaled
with match count, not with the result limit. Instead the selection
itself runs on device: key = trace start time under the mask,
`lax.top_k`, gather the per-trace counts at the winners, and return ONE
small fused int32 vector `[sids | counts | valid | n_match]` -- a single
fetch whose size is O(k), so query cost is O(limit) past the filter
kernel no matter how many traces matched.

Host re-verification may reject candidates (conservative device
encodings), so callers over-select and escalate k (db/search.py's
collect loop). The numpy variant serves the host evaluation path
(ops/hostfilter.py) with identical ordering semantics.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from .device import scoped

_NEG = -(2**31)


def k_bucket(k: int) -> int:
    """Power-of-two k so escalation reuses few compiled programs."""
    b = 16
    while b < k:
        b <<= 1
    return b


@lru_cache(maxsize=64)
def _compiled_select(k: int):
    @jax.jit
    @scoped("select")
    def sel(mask, key, counts):
        keyed = jnp.where(mask, key.astype(jnp.int32), jnp.int32(_NEG))
        _, topi = jax.lax.top_k(keyed, k)
        valid = jnp.take(mask, topi).astype(jnp.int32)
        return jnp.concatenate([
            topi.astype(jnp.int32),
            jnp.take(counts, topi).astype(jnp.int32),
            valid,
            jnp.sum(mask.astype(jnp.int32))[None],
        ])

    return sel


def select_topk_device(mask, key, counts, k: int):
    """mask/key/counts: same-length device (or host) arrays; k <= len.
    Returns (sids desc-by-key, counts at sids, n_match) as numpy --
    one device sync total."""

    from ..util.kerneltel import TEL

    k = int(min(k, mask.shape[0]))
    from ..util import costmodel

    sel = _compiled_select(k)
    with TEL.launch("select", ("sel1", k, int(mask.shape[0])), k,
                      cost=lambda: costmodel.spec(sel, mask, key, counts)):
        out = np.asarray(sel(mask, key, counts))
    sids, cnts, valid = out[:k], out[k : 2 * k], out[2 * k : 3 * k] > 0
    return sids[valid], cnts[valid], int(out[3 * k])


def group_rung(n_blocks: int) -> int:
    """Slots of a group's select: the power of two at or above its block
    count (1, 2, 4, ... MAX_BLOCKS_PER_BATCH), so a blocklist's jobs reach
    few programs whatever their sizes."""
    return 1 << max(n_blocks - 1, 0).bit_length()


@lru_cache(maxsize=64)
def _compiled_select_group(k: int, rung: int, part_len: int):
    """Fused cross-block selection: concatenate `rung` per-block (mask,
    key, count) vectors of `part_len` ON DEVICE and top-k once."""

    @jax.jit
    @scoped("select")
    def sel(masks, keys, counts):
        m = jnp.concatenate(masks)
        key = jnp.concatenate(keys).astype(jnp.int32)
        c = jnp.concatenate(counts)
        keyed = jnp.where(m, key, jnp.int32(_NEG))
        _, topi = jax.lax.top_k(keyed, k)
        valid = jnp.take(m, topi).astype(jnp.int32)
        return jnp.concatenate([
            topi.astype(jnp.int32),
            jnp.take(c, topi).astype(jnp.int32),
            valid,
            jnp.sum(m.astype(jnp.int32))[None],
        ])

    return sel


@lru_cache(maxsize=16)
def _absent_part(part_len: int):
    """(mask, key, count) of a slot no device block fills: nothing matches."""
    return (jnp.zeros(part_len, jnp.bool_), jnp.zeros(part_len, jnp.int32),
            jnp.zeros(part_len, jnp.int32))


@lru_cache(maxsize=64)
def _compiled_widen(part_len: int):
    """A block's vector brought to its group's part length (a group whose
    blocks' trace buckets differ): zeros behind, which match nothing."""
    return jax.jit(lambda x: jnp.pad(x, (0, part_len - x.shape[0])))


def select_topk_device_multi(masks, keys, counts, k: int, slots: int,
                             part_len: int):
    """Top-k across MANY blocks' device mask/key/count vectors in one
    fused program -> ONE device sync for the whole multi-block query.

    The program's shape is the GROUP's, not the parts': `slots` is the
    number of blocks of the job (device- and host-routed alike) and
    `part_len` its largest trace bucket, so the compile key is (k,
    group_rung(slots), part_len) whichever blocks the router sent to the
    device for this query; slots no part fills are masked out. Returns
    (global_idx desc-by-key, counts at winners, total n_match);
    global_idx // part_len is the part, global_idx % part_len its sid."""

    from ..util.kerneltel import TEL

    rung = group_rung(slots)
    k = int(min(k, rung * part_len))
    widen = _compiled_widen(part_len)
    parts = [tuple(x if x.shape[0] == part_len else widen(x) for x in part)
             for part in zip(masks, keys, counts)]
    parts += [_absent_part(part_len)] * (rung - len(parts))
    masks, keys, counts = (tuple(col) for col in zip(*parts))
    from ..util import costmodel

    sel = _compiled_select_group(k, rung, part_len)
    with TEL.launch(
        "select", ("selN", k, rung, part_len), k,
        cost=lambda: costmodel.spec(sel, masks, keys, counts), rung=rung):
        out = np.asarray(sel(masks, keys, counts))
    gids, cnts, valid = out[:k], out[k : 2 * k], out[2 * k : 3 * k] > 0
    return gids[valid], cnts[valid], int(out[3 * k])


def select_topk_host_multi(masks, keys, counts, k: int):
    """Host twin of select_topk_device_multi: one global top-k over many
    blocks' (mask, key, count) vectors. Keys must already be globally
    comparable (the cross-block gkey convention); returned ids index the
    concatenation of the parts."""
    return select_topk_host(
        np.concatenate(masks), np.concatenate(keys), np.concatenate(counts), k)


def select_topk_host(mask: np.ndarray, key: np.ndarray, counts: np.ndarray, k: int):
    """Numpy twin: argpartition + sort, same descending-key order."""
    n = mask.shape[0]
    n_match = int(np.count_nonzero(mask))
    k = int(min(k, n))
    keyed = np.where(mask, key.astype(np.int64), np.int64(-(2**62)))
    if k < n:
        part = np.argpartition(-keyed, k - 1)[:k] if k > 0 else np.empty(0, np.int64)
    else:
        part = np.arange(n)
    part = part[np.argsort(-keyed[part], kind="stable")]
    sids = part[mask[part]]
    return sids.astype(np.int64), counts[sids], n_match
