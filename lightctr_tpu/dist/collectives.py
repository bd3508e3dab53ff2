"""Collectives: explicit ring all-reduce, ring broadcast, psum wrappers.

The reference implements ring all-reduce by hand over ZeroMQ
(``distribut/ring_collect.h``): params fused into one flat buffer
(BufferFusion), split into ``ring_size`` segments (ring_collect.h:86-109),
N-1 reduce-scatter steps + N-1 all-gather steps around the ring neighbors
(ring_collect.h:48-72), each step a send_sync + out-of-order-tolerant receive,
finally dividing by N.

On TPU the *production* path is simply ``psum``/sharded-grad jit — XLA lowers
it to the ICI ring for us (``psum_all_reduce``).  ``ring_all_reduce`` below is
the explicit algorithm — same segment schedule as the reference — written with
``shard_map`` + ``lax.ppermute``, kept for two reasons: it is the benchmark
parity artifact (BASELINE.md 4-node ring run), and it is the template for
custom overlapping schedules XLA's default doesn't give.

Flattening a param pytree into one vector (``ravel_pytree``) plays the role of
``BufferFusion`` (buffer_fusion.h:53-65): N discontiguous tensors treated as
one logical flat buffer for the collective.
"""

from __future__ import annotations

import os
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, PartitionSpec as P


def _ring_perm(n: int):
    """Neighbor table: rank j sends to (j+1) % n (ring_collect.h:26-40)."""
    return [(j, (j + 1) % n) for j in range(n)]


def _ring_all_reduce_local(
    flat: jax.Array,
    axis_name: str,
    n: int,
    average: bool,
    compress_bits: int | None = None,
    compress_range: float | str = 1.0,
    residual: jax.Array | None = None,
    compress_mode: str = "uniform",
):
    """Runs per-device under shard_map.  ``flat`` is this device's full-length
    gradient vector, pre-padded to a multiple of n.

    ``residual``: optional same-shape error-feedback carry (EF-SGD).  Every
    value this member ENCODES during the exchange is first compensated with
    the residual of the step before, and the fresh quantization error is
    returned for the caller to carry into the next step — the bias of the
    codec becomes a delayed contribution instead of a loss.  Each segment
    slot is encoded exactly once per call (reduce phase sends slots
    idx, idx-1, ..., idx-(n-2); the gather phase encodes the remaining
    own=(idx+1)%n slot), so one [n, seg] buffer carries the whole state.
    Returns ``(reduced, new_residual)`` when a residual is given, else just
    ``reduced``."""
    idx = jax.lax.axis_index(axis_name)
    perm = _ring_perm(n)
    segs = flat.reshape(n, -1)

    if compress_bits is not None:
        from lightctr_tpu.ops import quantize, sparse_kernels

        use_ef = residual is not None
        res = (residual.reshape(n, -1) if use_ef
               else jnp.zeros_like(segs))
        if compress_range == "dynamic":
            # ring-global gradient magnitude: ONE fp32 pmax per call
            # (negligible next to the coded segments).  The codec's
            # resolution then TRACKS the gradient scale as training
            # converges — a fixed range turns late-training small gradients
            # into pure bucket noise, which is exactly what dragged the
            # int8 ring's accuracy (the reference rebuilds its
            # QuantileCompress tables from the data it ships,
            # quantile_compress.h:71-107; this is that policy as one
            # collective).  1.05 headroom keeps exact-max values off the
            # clip boundary.
            gmag = jnp.max(jnp.abs(segs))
            if not average:
                gmag = gmag * n  # partial SUMS must fit, not partial means
            if use_ef:
                # Every encoded value is val + res, and the carried residual
                # was bounded by half a bucket of the PREVIOUS table — which
                # may have been much wider if the gradient scale dropped
                # sharply between steps.  Measure the residual too (one
                # stacked pmax, still a single collective) so the 1.05
                # headroom is a real clip-free bound, not a slowly-varying-
                # scale assumption.  res already lives in the encoded
                # domain (/n partial means in average mode, raw sums
                # otherwise), so the two maxima add directly.
                mags = jax.lax.pmax(
                    jnp.stack([gmag, jnp.max(jnp.abs(res))]), axis_name
                )
                rng = 1.05 * (mags[0] + mags[1])
            else:
                rng = 1.05 * jax.lax.pmax(gmag, axis_name)
            rng = jnp.maximum(rng, 1e-12)
        else:
            rng = compress_range
        table = quantize.build_table(
            -rng, rng, bits=compress_bits, mode=compress_mode,
        )

        if average:
            # pre-divide by n so every partial sum in the reduce phase is a
            # partial MEAN, bounded by max|g| — otherwise mid-ring sums grow
            # toward n*max|g| and saturate the table (systematic clipping,
            # not noise).  The final /n below is skipped in this mode.
            # The residual lives in this same /n domain across steps.
            segs = segs / n

        # The hop payload is the uint8/uint16 CODES — decode happens on the
        # receiving device, so the interconnect moves 1-2 bytes/element, the
        # way the reference's fp16/int8 codec shrinks every ring Buffer it
        # ships (ring_collect.h + buffer.h:140-149).  extract(compress(x)) is
        # deterministic, so decoding receiver-side is bit-identical to the
        # sender's own decoded view.
        def rs_step(i, carry):
            segs, res = carry
            send_idx = (idx - i) % n
            val = jnp.take(segs, send_idx, axis=0)
            if use_ef:
                val = val + jnp.take(res, send_idx, axis=0)
            # the ring codec's pack step rides the kernel registry
            codes = sparse_kernels.quantize_pack(table, val)
            if use_ef:
                res = res.at[send_idx].set(
                    val - quantize.extract(table, codes)
                )
            recv = jax.lax.ppermute(codes, axis_name, perm)
            segs = segs.at[(idx - i - 1) % n].add(
                quantize.extract(table, recv)
            )
            return segs, res

        segs, res = jax.lax.fori_loop(
            0, n - 1, rs_step, (segs, res)
        )  # reduce-scatter
        # rank idx now owns fully-reduced segment (idx + 1) % n.  The
        # all-gather circulates CODES end to end: the owner encodes once and
        # every rank (owner included) reconstructs through the same table, so
        # replicas cannot diverge.  Slots other than `own` start as zeros but
        # each ag hop forwards only the segment received the previous hop, so
        # uninitialized slots never ride the wire.
        own = (idx + 1) % n
        code_dtype = jnp.uint8 if compress_bits <= 8 else jnp.uint16
        own_val = jnp.take(segs, own, axis=0)
        if use_ef:
            own_val = own_val + jnp.take(res, own, axis=0)
        own_codes = sparse_kernels.quantize_pack(table, own_val)
        if use_ef:
            res = res.at[own].set(
                own_val - quantize.extract(table, own_codes)
            )
        codes = jnp.zeros(segs.shape, code_dtype)
        codes = codes.at[own].set(own_codes)

        def ag_step(i, codes):
            send_idx = (idx + 1 - i) % n
            buf = jnp.take(codes, send_idx, axis=0)
            recv = jax.lax.ppermute(buf, axis_name, perm)
            return codes.at[(idx - i) % n].set(recv)

        codes = jax.lax.fori_loop(0, n - 1, ag_step, codes)  # all-gather
        out = quantize.extract(table, codes).reshape(-1)
        if use_ef:
            return out, res.reshape(-1)
        return out

    def rs_step(i, segs):
        send_idx = (idx - i) % n
        buf = jnp.take(segs, send_idx, axis=0)
        recv = jax.lax.ppermute(buf, axis_name, perm)
        return segs.at[(idx - i - 1) % n].add(recv)

    segs = jax.lax.fori_loop(0, n - 1, rs_step, segs)  # reduce-scatter
    # rank idx now owns fully-reduced segment (idx + 1) % n.

    def ag_step(i, segs):
        send_idx = (idx + 1 - i) % n
        buf = jnp.take(segs, send_idx, axis=0)
        recv = jax.lax.ppermute(buf, axis_name, perm)
        return segs.at[(idx - i) % n].set(recv)

    segs = jax.lax.fori_loop(0, n - 1, ag_step, segs)  # all-gather
    out = segs.reshape(-1)
    if average:
        out = out / n  # ring_collect.h:61-68 divides by ring size
    return out


def ef_residual_init(mesh, stacked_tree, axis: str = "data"):
    """Zero error-feedback carry for :func:`ring_all_reduce`'s EF mode:
    one padded flat vector per ring member, stacked on the ring axis."""
    import numpy as np

    n = mesh.shape[axis]
    length = sum(
        int(np.prod(x.shape[1:]))
        for x in jax.tree_util.tree_leaves(stacked_tree)
    )
    padded = ((length + n - 1) // n) * n
    return jnp.zeros((n, padded), jnp.float32)


def ring_all_reduce(
    mesh: Mesh,
    stacked_tree,
    axis: str = "data",
    average: bool = True,
    compress_bits: int | None = None,
    compress_range: float | str = 1.0,
    compress_mode: str = "uniform",
    residual=None,
):
    """Explicit ring all-reduce of per-device gradient pytrees.

    ``stacked_tree``: pytree whose leaves have a leading device dimension of
    size ``mesh.shape[axis]`` (one slice per ring member — the per-worker
    gradients).  Returns the same structure where every slice holds the
    reduced (mean by default) values.

    ``compress_bits``: when set (8 or 16), every transmitted segment is
    quantile-compressed to that width before the hop and decoded after — the
    reference compresses ALL its ring wire traffic the same way (fp16 codec
    on every Buffer, ring_collect.h + buffer.h:140-149; int8 via its
    QuantileCompress).  Quantization noise accumulates once per reduce hop.
    In ``average`` mode inputs are pre-divided by the ring size so partial
    sums stay within ``compress_range`` as long as it bounds a single
    gradient's magnitude; in ``average=False`` (sum) mode ``compress_range``
    must bound the FULL n-way sum or values clip.  Pass the string
    ``"dynamic"`` to measure the range per call instead (one ring-global
    ``pmax``; with error feedback the measurement includes the carried
    residual, so a sharp drop in gradient scale cannot clip last step's
    carry): the table then tracks the gradient scale through training,
    which is what keeps a low-bit codec accurate once gradients shrink far
    below any fixed range.

    ``residual``: optional per-member error-feedback carry (EF-SGD; build
    the initial zeros with :func:`ef_residual_init`).  When given, every
    encoded segment is compensated with the previous step's quantization
    error and the call returns ``(reduced_tree, new_residual)`` — carry the
    residual through the training loop.  The reference ships every ring
    Buffer through its codec and still reports ~1.0 accuracy
    (4_node_ring.png); EF is how a low-bit codec earns that.

    The whole exchange — BufferFusion flatten, padded ring schedule, codec,
    unflatten — runs per-device INSIDE one ``shard_map``, so the call is a
    single jittable program with no host staging: wrap it (or a step using
    it) in ``jax.jit`` and it serves as the production overlap-schedule
    template, not just the bench artifact.
    """
    n = mesh.shape[axis]
    use_ef = residual is not None
    if use_ef and compress_bits is None:
        raise ValueError("error-feedback residual needs compress_bits")

    def local(tree, res):
        # per-device slice: leaves arrive as [1, ...]
        per_dev = jax.tree_util.tree_map(lambda x: x[0], tree)
        # BufferFusion (buffer_fusion.h:53-65): one contiguous vector
        flat, unravel = ravel_pytree(per_dev)
        length = flat.shape[0]
        padded = ((length + n - 1) // n) * n
        if padded != length:
            flat = jnp.pad(flat, (0, padded - length))
        if use_ef:
            flat, new_res = _ring_all_reduce_local(
                flat, axis, n, average,
                compress_bits=compress_bits, compress_range=compress_range,
                residual=res[0], compress_mode=compress_mode,
            )
        else:
            flat = _ring_all_reduce_local(
                flat, axis, n, average,
                compress_bits=compress_bits, compress_range=compress_range,
                compress_mode=compress_mode,
            )
            new_res = res[0]
        out = unravel(flat[:length])
        return (jax.tree_util.tree_map(lambda x: x[None], out),
                new_res[None])

    fn = shard_map(local, mesh=mesh, in_specs=(P(axis), P(axis)),
                   out_specs=(P(axis), P(axis)))
    res_in = residual if use_ef else jnp.zeros((n, 1), jnp.float32)
    out, new_res = fn(stacked_tree, res_in)
    if use_ef:
        return out, new_res
    return out


def ring_broadcast(mesh: Mesh, stacked_tree, axis: str = "data"):
    """Rank-0's values circulated to every ring member — ``syncInitializer``
    parity (ring_collect.h:74-79)."""
    n = mesh.shape[axis]

    def local(x):
        # one hop per step: after n-1 steps all ranks hold rank 0's data
        def step(i, v):
            recv = jax.lax.ppermute(v, axis, _ring_perm(n))
            idx = jax.lax.axis_index(axis)
            # ranks > 0 adopt what arrives from the left on their turn
            return jnp.where((idx > i) & (idx <= i + 1), recv, v)

        return jax.lax.fori_loop(0, n - 1, step, x)

    fn = shard_map(local, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
    return jax.tree_util.tree_map(lambda leaf: fn(leaf.reshape((-1,) + leaf.shape[2:])).reshape(leaf.shape), stacked_tree)


def all_to_all_exchange(
    mesh: Mesh,
    stacked: jax.Array,
    axis: str = "data",
    compress_bits: int | None = None,
    compress_range: float | str = 1.0,
) -> jax.Array:
    """All-to-all block exchange — the collective under sharded-embedding
    push/pull (SURVEY.md §2.7: the reference's DHT-routed per-PS key batches
    become ``all_to_all`` on a mesh axis).

    ``stacked``: [n, n, ...] where slice [i, j] is the block device i holds
    FOR device j (e.g. the lookup requests i wants shard j to serve).
    Returns [n, n, ...] where slice [j, i] on device j is what i sent it —
    i.e. the transpose of the first two axes, moved over the interconnect.

    ``compress_bits``: when set (8 or 16), every float block is
    quantile-coded before the exchange and the uint8/uint16 CODES are what
    ride the interconnect; decode happens on the receiving device — the
    PS-traffic counterpart of the ring codec (the reference fp16-codes EVERY
    value the PS serves or receives, paramserver.h:161-163).
    ``compress_range`` must bound the block magnitudes (embedding rows / row
    gradients) or they clip; the string ``"dynamic"`` measures it per call
    (one global ``pmax`` over the mesh axis), the same adaptive-table
    policy as :func:`ring_all_reduce`.  Integer payloads (key requests)
    ride through the separate varint host codec (`dist.wire.pack_varint`)
    or uncompressed.
    """
    n = mesh.shape[axis]
    if stacked.ndim < 2 or stacked.shape[0] != n or stacked.shape[1] != n:
        raise ValueError(
            f"expected leading dims [{n}, {n}, ...], got {stacked.shape}"
        )
    if compress_bits is not None and not jnp.issubdtype(
        stacked.dtype, jnp.floating
    ):
        raise ValueError(
            f"compress_bits needs a float payload, got {stacked.dtype}"
        )

    if compress_bits is not None:
        from lightctr_tpu.ops import quantize

        def local(x):  # x: [1, n, ...] this device's outgoing blocks
            if compress_range == "dynamic":
                rng = 1.05 * jax.lax.pmax(jnp.max(jnp.abs(x)), axis)
                rng = jnp.maximum(rng, 1e-12)
            else:
                rng = compress_range
            # all senders share one table (the pmax is axis-global), so
            # every receiver decodes exactly what was encoded
            table = quantize.build_table(
                -rng, rng, bits=compress_bits, mode="uniform"
            )
            # encode BEFORE the collective so the all_to_all operand is the
            # narrow code array; decode after, on the receiver
            codes = jax.lax.all_to_all(
                quantize.compress(table, x), axis, split_axis=1, concat_axis=1
            )
            return quantize.extract(table, codes)
    else:
        def local(x):  # x: [1, n, ...] this device's outgoing blocks
            # concat on the same axis keeps the received blocks sender-indexed
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=1)

    fn = shard_map(local, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
    return fn(stacked)


# ---------------------------------------------------------------------------
# Sparsity-aware gradient exchange (SparCML, arXiv:1802.08021; Parallax,
# arXiv:1808.02621).  CTR gradients touch a few thousand rows of a 2^20-row
# table; exchanging the dense [vocab, dim] gradient pays O(vocab) bytes per
# step.  Here each member contributes its deduped (uids, rows) pair — fixed
# padded shape, so the whole exchange jits — one all_gather moves
# O(touched) ids+values, and duplicates merge with a segment_sum.  The
# density-based switch back to the dense ring (SparCML's dense fallback) is
# a STATIC trace-time policy: our sparse payload is padded to the batch's
# nnz, so the exchanged byte count is known from shapes alone and the worst
# case never regresses past the dense path.


def _wire_value_bytes(compress_bits: int | None) -> int:
    return 4 if compress_bits is None else (1 if compress_bits <= 8 else 2)


def _wire_row_bytes(dim: int, compress_bits: int | None) -> int:
    """Wire bytes of ONE row of ``dim`` values under the codec: fp32
    (None), 2-byte codes (9..16 bits), 1-byte codes (5..8 bits), or the
    BIT-PACKED sub-byte codes (<= 4 bits: two codes per byte, odd dim
    rounds up — ``ops.quantize.pack_nibbles``)."""
    if compress_bits is None:
        return int(dim) * 4
    if compress_bits <= 4:
        return (int(dim) + 1) // 2
    return int(dim) * _wire_value_bytes(compress_bits)


def sparse_exchange_bytes(
    n: int, k_padded: int, dim: int, compress_bits: int | None = None,
    include_ids: bool = True,
) -> int:
    """Bytes each member TRANSMITS per :func:`sparse_all_reduce` call: the
    ring all_gather forwards each of the other members' [k_padded] id +
    [k_padded, dim] value segments once (n-1 hop payloads of one segment
    each); values are fp32 or 1/2-byte codes when compressed, ids int32.
    ``include_ids=False`` prices a table that RIDES a shared id stream
    (several tables listing the same batch fields gather the ids once —
    only the first table in the group pays the id bytes)."""
    idb = 4 if include_ids else 0
    return int((n - 1) * int(k_padded)
               * (idb + _wire_row_bytes(dim, compress_bits)))


def dense_ring_bytes(
    vocab: int, dim: int, n: int, compress_bits: int | None = None
) -> int:
    """Bytes each member transmits per dense all-reduce of a [vocab, dim]
    gradient: reduce-scatter + all-gather each move (n-1) segments of
    vocab*dim/n values (ring_all_reduce's schedule; psum lowers to the
    same ring)."""
    return int(2 * (n - 1) * int(vocab)
               * _wire_row_bytes(dim, compress_bits) // n)


def prefer_sparse_exchange(
    n: int,
    k_padded: int,
    vocab: int,
    dim: int,
    sparse_bits: int | None = None,
    dense_bits: int | None = None,
    margin: float = 1.0,
) -> bool:
    """SparCML's density switch (arXiv:1802.08021 §3: sparse index+value
    streams until density makes the dense representation cheaper), decided
    from static shapes: True when the padded sparse payload is cheaper than
    ``margin`` times the dense ring's bytes.  ``margin < 1`` demands a real
    win before leaving the dense path (hysteresis against payloads that are
    only marginally sparse)."""
    return (sparse_exchange_bytes(n, k_padded, dim, sparse_bits)
            <= margin * dense_ring_bytes(vocab, dim, n, dense_bits))


# -- v2: owner-partitioned reduce-scatter sparse exchange --------------------
#
# The allgather variant above replicates every member's FULL (uids, g_rows)
# payload to every peer: each member transmits (n-1)*K entries and holds
# n*K rows for the merge.  SparCML's split-allreduce (arXiv:1802.08021 §4)
# instead routes each contribution to the id's OWNER, merges there, and
# broadcasts only the merged union.  Here: ids are owner-partitioned by the
# same modulo family as the PS key router (dist/partition.py
# ModuloPartition — owner = uid % n), destination buckets ride a
# lax.ppermute ring (one bucket per hop), the owner merges duplicates with
# one segment_sum, and an all_gather moves only the merged owner shards.
# Per-member traffic is (n-1)*(bucket_cap + shard_cap) entries — with
# bucket_cap ~ K/n and shard_cap ~ union/n that is O(touched) TOTAL, flat
# in world size, where the allgather variant's (n-1)*K grows linearly.
#
# Static shapes force the two capacities to be chosen at trace time.  The
# worst case (every id hashed to one owner) cannot be bounded below K
# without overflow, so the capacities are EXPECTED sizes with slack
# (:func:`rs_default_caps`) and the collective reports an in-jit overflow
# count; callers that must stay exact (the hybrid trainer) run the cheap
# host-side :func:`rs_fits` check per batch and fall back to the allgather
# program for the rare batch that would overflow — correctness never
# depends on the capacity guess.

#: slack multiplier on the expected bucket / merged-shard sizes — absorbs
#: the Poisson fluctuation of uniform-ish id streams around K/n per owner
RS_SLACK = 1.3


def rs_default_caps(
    n: int, k_padded: int, vocab: int, slack: float = RS_SLACK
) -> tuple[int, int]:
    """(bucket_cap, shard_cap) for :func:`sparse_reduce_scatter`, from
    static shapes only.  ``bucket_cap`` bounds one member's contributions
    to one owner (expected K/n, never more than min(K, ceil(vocab/n)) —
    deduped ids owned by one owner cannot exceed the owner's id range);
    ``shard_cap`` bounds the merged unique ids per owner (expected
    union/n under a uniform-id estimate, never more than
    min(n*bucket_cap, ceil(vocab/n) + 1) — the +1 is the id-0 padding
    slot that may ride along in every shard)."""
    k = max(1, int(k_padded))
    owned = -(-int(vocab) // n)  # ceil(vocab / n)
    bucket = min(k, owned, max(1, -(-int(slack * k) // n)))
    density = min(k / float(vocab), 1.0)
    u_hat = float(vocab) * (1.0 - (1.0 - density) ** n)
    shard = min(n * bucket, owned + 1,
                max(bucket, int(slack * u_hat / n) + 2))
    return bucket, shard


def sparse_rs_bytes(
    n: int,
    bucket_cap: int,
    shard_cap: int,
    dim: int,
    compress_bits: int | None = None,
    include_ids: bool = True,
) -> int:
    """Bytes each member transmits per :func:`sparse_reduce_scatter` call:
    n-1 destination buckets (one per ppermute hop) in the scatter phase
    plus n-1 merged-shard segments in the all-gather phase, each entry an
    int32 id + dim coded/fp32 values.  ``include_ids=False`` prices a
    table riding a shared id stream (ids exchanged once per group)."""
    idb = 4 if include_ids else 0
    per_entry = idb + _wire_row_bytes(dim, compress_bits)
    return int((n - 1) * (int(bucket_cap) + int(shard_cap)) * per_entry)


#: extra hysteresis the reduce-scatter variant must clear against the DENSE
#: ring: its n-1 ppermute rounds plus the owner-side sort/unique merge cost
#: real latency the byte model does not see, so a near-tie on bytes (the
#: measured 2^14 bench cell: rs 1.0006x dense, >2x slower wall-clock on the
#: CPU mesh) must not flip the policy off the worst-case-safe dense path.
#: rs-vs-allgather stays a plain byte comparison — both are sparse
#: collectives with comparable per-entry work.
RS_DENSE_MARGIN = 0.9


# -- hierarchical two-level exchange: bandwidth model + byte accounting ------
#
# A multi-HOST deployment has two fabrics: the intra-host interconnect (ICI
# — the mesh the in-jit collectives above run on) and the cross-host
# datacenter network (DCN — the socket PS wire of dist/hier.py).  A flat
# collective spanning both runs at the SLOWEST link's speed: every ring/
# ppermute schedule above pipelines one segment per hop, so the hop crossing
# the DCN gates the whole exchange.  The hierarchical exchange instead
# aggregates WHERE THE DATA CROSSES THE SLOW LINK (the in-network-aggregation
# argument, arXiv:2205.05243, on SparCML-style sparse payloads): replicas
# merge over ICI first, then exactly ONE merged (uids, rows) payload per host
# rides the DCN — cross-host bytes O(touched-per-host) regardless of local
# replica count.  The pick between the flat algorithms and the hierarchy is
# therefore a TIME comparison over measured link bandwidths, not a byte
# comparison on one fabric.

#: fallback link speeds (bytes/s) when neither the env override nor a probe
#: supplied a measurement: a v4-ish ICI link vs a 2x25GbE-ish DCN share —
#: the ~16x gap typical of TPU pods, so the un-probed default already
#: prefers aggregation before the slow link
DEFAULT_ICI_BPS = 4.0e9
DEFAULT_DCN_BPS = 2.5e8

#: env override: ``LIGHTCTR_LINK_BW="<ici_bytes_per_s>:<dcn_bytes_per_s>"``
LINK_BW_ENV = "LIGHTCTR_LINK_BW"


class LinkBandwidth(NamedTuple):
    """Measured (or configured) fabric speeds the cost model prices with.
    ``source``: "env" | "probe" | "default" — artifacts record where the
    numbers came from, so a defaulted model can't masquerade as measured."""

    ici_bps: float
    dcn_bps: float
    source: str = "default"


_link_bw_cache: Optional[LinkBandwidth] = None


def link_bandwidth(
    probe_ici=None, probe_dcn=None, refresh: bool = False
) -> LinkBandwidth:
    """The process's link-bandwidth estimate, resolved once and cached
    (re-probing every trace would make the trace-time pick flap with probe
    noise — the measurement is sticky by construction; ``refresh=True``
    re-resolves).  Priority: :data:`LINK_BW_ENV` override, then the probe
    callables (zero-arg -> bytes/s; e.g. :func:`measure_ici_bw` /
    ``HierExchangeClient.probe_bw``), then the documented defaults.  A
    cached DEFAULT resolution never shadows a later call that brings
    probes: an early probe-less ``pick_exchange_algo`` must not pin the
    fallback numbers for the whole process."""
    global _link_bw_cache
    if _link_bw_cache is not None and not refresh:
        if _link_bw_cache.source != "default" or (
                probe_ici is None and probe_dcn is None):
            return _link_bw_cache
    env = os.environ.get(LINK_BW_ENV, "").strip()
    if env:
        ici_s, _, dcn_s = env.partition(":")
        bw = LinkBandwidth(float(ici_s), float(dcn_s or ici_s), "env")
    else:
        ici = dcn = None
        try:
            ici = float(probe_ici()) if probe_ici is not None else None
        except Exception:  # a failed probe degrades to the default, loudly
            import logging

            logging.getLogger(__name__).warning(
                "ICI bandwidth probe failed; using default", exc_info=True
            )
        try:
            dcn = float(probe_dcn()) if probe_dcn is not None else None
        except Exception:
            import logging

            logging.getLogger(__name__).warning(
                "DCN bandwidth probe failed; using default", exc_info=True
            )
        source = "probe" if (ici is not None or dcn is not None) else "default"
        bw = LinkBandwidth(ici or DEFAULT_ICI_BPS, dcn or DEFAULT_DCN_BPS,
                           source)
    if bw.ici_bps <= 0 or bw.dcn_bps <= 0:
        raise ValueError(f"link bandwidths must be positive, got {bw}")
    _link_bw_cache = bw
    return bw


def measure_ici_bw(mesh: Mesh, axis: str = "data",
                   payload_bytes: int = 1 << 22, reps: int = 3) -> float:
    """Startup ICI probe: median post-compile wall time of one tiled
    ``all_gather`` of a ``payload_bytes`` fp32 vector over the mesh axis ->
    bytes each member transmitted per second ((n-1)/n of the gathered
    array rides this member's outgoing link)."""
    n = mesh.shape[axis]
    if n < 2:
        return DEFAULT_ICI_BPS
    per = max(1, payload_bytes // 4 // n)
    x = jnp.zeros((n, per), jnp.float32)

    def local(v):
        return jax.lax.all_gather(v[0], axis, tiled=True)[None]

    fn = jax.jit(shard_map(local, mesh=mesh, in_specs=P(axis),
                           out_specs=P(axis)))
    jax.block_until_ready(fn(x))  # compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        ts.append(time.perf_counter() - t0)
    moved = (n - 1) * per * 4  # bytes through one member's outgoing link
    return moved / max(float(np.median(ts)), 1e-9)


def expected_union(k: int, vocab: int, members: int) -> int:
    """Expected unique-id union of ``members`` independent K-id streams
    over ``vocab`` rows (the same uniform-id estimator
    :func:`rs_default_caps` sizes its shards with)."""
    density = min(max(int(k), 1) / float(max(int(vocab), 1)), 1.0)
    u = float(vocab) * (1.0 - (1.0 - density) ** max(int(members), 1))
    return max(1, min(int(u) + 1, int(vocab), int(members) * int(k)))


def hier_wire_bytes(
    k_out: int, k_in: int, dim: int, wire_bits: int | None = None,
    include_ids: bool = True,
) -> int:
    """Bytes ONE HOST moves over the DCN per hierarchical exchange of one
    table: push its ``k_out`` locally-merged entries + pull the
    ``k_in``-entry cross-host union, each entry an id plus ``dim`` values
    (``wire_bits`` None = the exact fp32 wire codec, 16 = the PS fp16
    codec, 5..8 = 1-byte codes — the client's ``q8_ef`` frame, <=4 =
    bit-packed nibble codes at two per byte — the client's ``q4_ef``
    frame, ``ops.quantize.pack_nibbles`` order on the wire).  Every
    priced width is a codec ``HierExchangeClient`` actually ships.  Flat
    in local replica count by construction — the replicas merged before
    the wire."""
    idb = 4 if include_ids else 0
    per = idb + _wire_row_bytes(dim, wire_bits)
    return int((int(k_out) + int(k_in)) * per)


def hier_exchange_bytes(
    local_n: int,
    n_hosts: int,
    k_padded: int,
    vocab: int,
    dim: int,
    sparse_bits: int | None = None,
    wire_bits: int | None = None,
    slack: float = RS_SLACK,
) -> tuple[str, int, int]:
    """Static-shape byte model of the two-level exchange ->
    ``(local_algo, local_ici_bytes, dcn_wire_bytes)``: the intra-host
    merge rides the cheaper of the two in-jit sparse collectives over the
    ``local_n``-replica mesh (``local_algo``), then one merged payload per
    host (expected union of the local streams) is pushed and the expected
    cross-host union pulled over the DCN.  ``k_padded`` is the PER-REPLICA
    padded id count, as everywhere in this module."""
    ag_b = sparse_exchange_bytes(local_n, k_padded, dim, sparse_bits)
    bucket, shard = rs_default_caps(local_n, k_padded, vocab, slack)
    rs_b = sparse_rs_bytes(local_n, bucket, shard, dim, sparse_bits)
    local_algo, local_b = (
        ("sparse", ag_b) if ag_b <= rs_b else ("sparse_rs", rs_b)
    )
    if local_n <= 1:
        local_algo, local_b = "none", 0
    k_out = expected_union(k_padded, vocab, local_n)
    k_in = expected_union(k_padded, vocab, local_n * n_hosts)
    return local_algo, local_b, hier_wire_bytes(k_out, k_in, dim, wire_bits)


#: hysteresis the HIERARCHICAL pick must clear against the best flat
#: algorithm's modeled time: the wire stage pays a push+pull round trip,
#: host staging and the reduce rendezvous barrier that a pure
#: bytes/bandwidth model does not see — a near-tie stays on the flat path
#: (the same contract as :data:`RS_DENSE_MARGIN`)
HIER_DCN_MARGIN = 0.9

#: switch-away hysteresis when a previous pick is supplied: the challenger
#: must beat the incumbent's modeled time by this factor before the pick
#: moves — bandwidth re-probes jitter a few percent run to run, and a
#: per-table algorithm that flaps re-traces the whole step program
PICK_FLAP_MARGIN = 0.8


def pick_exchange_algo(
    n: int,
    k_padded: int,
    vocab: int,
    dim: int,
    sparse_bits: int | None = None,
    dense_bits: int | None = None,
    margin: float = 1.0,
    slack: float = RS_SLACK,
    rs_margin: float = RS_DENSE_MARGIN,
    local_n: int | None = None,
    bw: LinkBandwidth | None = None,
    wire_bits: int | None = None,
    prev: str | None = None,
    hier_margin: float = HIER_DCN_MARGIN,
    stripes: int = 1,
    overlap_push: bool = False,
) -> tuple[str, int]:
    """Trace-time exchange pick -> ``(algo, bytes)``.

    SINGLE-FABRIC form (``local_n`` None or == ``n``): the three-way byte
    pick of PR 5 (SparCML's density switch with the reduce-scatter
    option) — ``"dense" | "sparse" | "sparse_rs"`` from static shapes
    alone.  The cheaper sparse variant must still beat ``margin`` times
    the dense ring, the reduce-scatter variant additionally ``rs_margin``
    times it (:data:`RS_DENSE_MARGIN`); otherwise the worst-case-safe
    dense path wins.

    TWO-FABRIC form (``local_n`` < ``n``, i.e. ``n_hosts = n / local_n``
    hosts of ``local_n`` replicas): a bandwidth-aware COST model.  The
    flat algorithms schedule host-oblivious — of each member's ``B``
    transmitted bytes, the off-host peer share ``(n - local_n)/(n - 1)``
    crosses a host boundary, and the host's ``local_n`` members share ONE
    DCN uplink — so their modeled time is
    ``local_n * B * cross / dcn_bps + B * (1 - cross) / ici_bps``.  The
    ``"hier"`` candidate aggregates before the slow link (the in-network-
    aggregation move): ``local_bytes / ici_bps + wire_bytes / dcn_bps``
    (:func:`hier_exchange_bytes`) — the uplink carries one merged payload
    per host instead of every replica's, which is exactly why cross-host
    bytes stay flat in ``local_n``.  ``bw`` defaults to the process's
    cached :func:`link_bandwidth` (env override / probe / default).
    ``hier`` must beat the best flat candidate by ``hier_margin``
    (:data:`HIER_DCN_MARGIN`), and with ``prev`` given the incumbent
    keeps the pick unless the challenger wins by
    :data:`PICK_FLAP_MARGIN` — two hystereses so the pick never flaps on
    probe noise.  For the hier branch the returned bytes are the DCN WIRE
    bytes per host (the scarce resource the pick is protecting);
    ``wire_bits`` prices the wire codec (None = exact fp32, 16 = the PS
    fp16 codec, 8 = the client's q8_ef frame, 4 = the client's q4_ef
    nibble frame — see :func:`hier_wire_bytes`).

    STREAMING rendezvous terms (ISSUE 16): ``stripes`` is the number of
    rendezvous shards a table's id space is striped across — aggregate
    DCN bandwidth scales with shard count, so the hier wire sees
    ``stripes ×`` the per-link rate (the flat candidates ride in-jit
    collectives and do not stripe).  ``overlap_push=True`` prices the
    dispatch/commit ticket: the chunked push of step N transmits while
    the NEXT step's local merge computes, so the hier time is
    ``max(local_t, push_t) + pull_t`` instead of the serial sum — only
    the pull stays on the critical path when the push hides under
    compute."""
    dense_b = dense_ring_bytes(vocab, dim, n, dense_bits)
    ag_b = sparse_exchange_bytes(n, k_padded, dim, sparse_bits)
    bucket, shard = rs_default_caps(n, k_padded, vocab, slack)
    rs_b = sparse_rs_bytes(n, bucket, shard, dim, sparse_bits)

    def flat_pick() -> tuple[str, int]:
        algo, sb = ("sparse", ag_b) if ag_b <= rs_b else ("sparse_rs", rs_b)
        eff = margin * (rs_margin if algo == "sparse_rs" else 1.0)
        if sb <= eff * dense_b:
            return algo, sb
        if algo == "sparse_rs" and ag_b <= margin * dense_b:
            # rs failed its stricter dense hysteresis but the allgather
            # still clears the plain density switch
            return "sparse", ag_b
        return "dense", dense_b

    if local_n is None or local_n >= n:
        return flat_pick()
    if n % local_n:
        raise ValueError(
            f"world {n} is not a whole number of {local_n}-replica hosts"
        )
    if bw is None:
        bw = link_bandwidth()
    n_hosts = n // local_n
    _, hier_local_b, hier_wire_b = hier_exchange_bytes(
        local_n, n_hosts, k_padded, vocab, dim,
        sparse_bits=sparse_bits, wire_bits=wire_bits, slack=slack,
    )
    flat_algo, flat_b = flat_pick()
    cross = (n - local_n) / (n - 1)  # off-host share of per-peer traffic

    def flat_time(b: int) -> float:
        return (local_n * b * cross / bw.dcn_bps
                + b * (1.0 - cross) / bw.ici_bps)

    # streaming terms: striped shards multiply the wire rate; an
    # overlapped push hides under the local merge (docstring above).
    # The push/pull split reuses the union estimator the combined
    # hier_wire_b was built from, so the two always sum consistently.
    dcn_eff = bw.dcn_bps * max(1, int(stripes))
    local_t = hier_local_b / bw.ici_bps
    if overlap_push:
        k_out = expected_union(k_padded, vocab, local_n)
        k_in = expected_union(k_padded, vocab, local_n * n_hosts)
        push_t = hier_wire_bytes(k_out, 0, dim, wire_bits) / dcn_eff
        pull_t = hier_wire_bytes(0, k_in, dim, wire_bits) / dcn_eff
        hier_t = max(local_t, push_t) + pull_t
    else:
        hier_t = local_t + hier_wire_b / dcn_eff

    times = {
        flat_algo: flat_time(flat_b),
        "hier": hier_t,
    }
    bytes_of = {flat_algo: flat_b, "hier": hier_wire_b}
    best = min(times, key=times.get)
    if best == "hier" and times["hier"] > hier_margin * times[flat_algo]:
        best = flat_algo  # near-tie: stay on the flat path
    if prev is not None and prev in times and best != prev:
        if times[best] > PICK_FLAP_MARGIN * times[prev]:
            best = prev  # incumbent keeps a contested pick
    return best, bytes_of[best]


def rs_fits(
    per_member_ids, n: int, bucket_cap: int, shard_cap: int
) -> bool:
    """Host-side exact capacity check for one batch (numpy, O(nnz log nnz)):
    True when every member's per-owner unique-id count fits ``bucket_cap``
    AND every owner's cross-member union fits ``shard_cap``.  ``per_member_
    ids``: one raw (pre-dedup) integer id array per mesh member.  The
    hybrid trainer runs this before dispatching the reduce-scatter step and
    falls back to the allgather program when it returns False, so the
    capacity guess can never corrupt a step."""
    uniques = []
    for ids in per_member_ids:
        u = np.unique(np.asarray(ids).reshape(-1))
        if u.size:
            counts = np.bincount((u % n).astype(np.int64), minlength=n)
            if counts.max(initial=0) > bucket_cap:
                return False
        uniques.append(u)
    gu = np.unique(np.concatenate(uniques)) if uniques else np.zeros(0)
    if not gu.size:
        return True
    counts = np.bincount((gu % n).astype(np.int64), minlength=n)
    # +1: the id-0 padding slot can ride into every owner's shard
    return bool(counts.max(initial=0) + 1 <= shard_cap)


def _coded_exchange(
    payload: jax.Array,
    exchange,
    axis_name: str,
    compress_bits: int,
    compress_range: float | str,
    compress_mode: str,
) -> jax.Array:
    """Single-shot quantile-coded collective: build ONE axis-global table
    (dynamic range = one pmax over the local payload, 1.05 headroom,
    1e-12 floor), encode, run ``exchange`` on the narrow codes, decode on
    the receiver.  Every coded sparse payload (allgather rows, rs buckets,
    rs merged shards) goes through here so the codec policy lives in one
    place (pack rides the kernel registry's ``quantize_pack``)."""
    from lightctr_tpu.ops import quantize, sparse_kernels

    if compress_range == "dynamic":
        rng = 1.05 * jax.lax.pmax(jnp.max(jnp.abs(payload)), axis_name)
        rng = jnp.maximum(rng, 1e-12)
    else:
        rng = compress_range
    table = quantize.build_table(
        -rng, rng, bits=compress_bits, mode=compress_mode,
    )
    return quantize.extract(
        table, exchange(sparse_kernels.quantize_pack(table, payload))
    )


def _ag_gather_ids(uids: jax.Array, axis_name: str):
    """Id half of the allgather sparse exchange: one tiled all_gather of the
    [K] id stream + the union/inverse mapping every member computes
    identically.  Split out so tables sharing one id stream (identical
    batch-field tuples) gather and dedup the ids ONCE — the row half
    (:func:`_ag_merge_rows`) reuses ``inv`` per table.  The dedup is
    ``ops.sparse_kernels.dedup_ids``: the ``jnp.unique`` contract by
    three sorts."""
    from lightctr_tpu.ops import sparse_kernels

    all_ids = jax.lax.all_gather(uids, axis_name, tiled=True)
    uniq, inv, _ = sparse_kernels.dedup_ids(all_ids)
    return all_ids, uniq, inv


def _ef_valid_mask(uids: jax.Array, like: jax.Array) -> jax.Array:
    """Broadcastable validity mask over an id stream: every slot except
    the padded id-0 repeats beyond slot 0 (the dedup convention) — pads
    must never touch row 0's EF carry."""
    k = uids.shape[0]
    valid = ~((uids == 0) & (jnp.arange(k) > 0))
    return valid.astype(like.dtype).reshape((-1,) + (1,) * (like.ndim - 1))


def _ag_exchange_rows(
    rows: jax.Array,
    axis_name: str,
    compress_bits: int | None = None,
    compress_range: float | str = "dynamic",
    compress_mode: str = "uniform",
    uids: jax.Array | None = None,
    residual: jax.Array | None = None,
):
    """Gather/decode half of the allgather sparse exchange (no merge, no
    averaging — the caller owns the /n): every member's [K, ...] payload,
    optionally quantile-coded, lands as [n*K, ...] decoded rows —
    ``(all_rows, new_residual | None)``.  The hybrid trainer consumes
    this directly and folds the merge (and the mean) into the fused
    merge-apply kernel; :func:`_ag_merge_rows` wraps it for callers that
    want the merged rows materialized.

    ``residual``: [vocab, ...] per-member error-feedback table for CLIPPED
    payloads under a FIXED ``compress_range`` (requires ``uids``): the
    carried remainder is compensated into this step's encode and the fresh
    clip+quantization error is scattered back at the rows' slots — the
    compensate/encode/decode/error/carry-scatter chain is
    ``sparse_kernels.quantize_pack_ef_update``."""
    use_ef = residual is not None
    if compress_bits is None:
        if use_ef:
            raise ValueError("sparse error feedback needs compress_bits")
        return jax.lax.all_gather(rows, axis_name, tiled=True), None
    if not use_ef:
        return _coded_exchange(
            rows,
            lambda c: jax.lax.all_gather(c, axis_name, tiled=True),
            axis_name, compress_bits, compress_range, compress_mode,
        ), None
    from lightctr_tpu.ops import quantize, sparse_kernels

    if not isinstance(compress_range, (int, float)):
        raise ValueError(
            "sparse error feedback compensates FIXED-range clipping; "
            "compress_range='dynamic' never clips — pass a float range"
        )
    if uids is None:
        raise ValueError("sparse error feedback needs uids")
    table = quantize.build_table(
        -compress_range, compress_range,
        bits=compress_bits, mode=compress_mode,
    )
    # every VALID slot (non-pad) compensates — including ids whose
    # gradient is zero this step, so a carried clip remainder drains on
    # the id's next appearance rather than waiting for a nonzero gradient.
    # The compensate/encode/decode/fresh-error/carry-scatter chain is
    # quantize_pack_ef_update.
    mask = _ef_valid_mask(uids, rows)
    codes, new_residual, _ = sparse_kernels.quantize_pack_ef_update(
        table, rows, uids, residual, mask
    )
    all_rows = quantize.extract(
        table, jax.lax.all_gather(codes, axis_name, tiled=True)
    )
    return all_rows, new_residual


def _ag_merge_rows(
    rows: jax.Array,
    inv: jax.Array,
    axis_name: str,
    n: int,
    num_segments: int,
    average: bool = True,
    compress_bits: int | None = None,
    compress_range: float | str = "dynamic",
    compress_mode: str = "uniform",
    uids: jax.Array | None = None,
    residual: jax.Array | None = None,
):
    """Row half of the allgather sparse exchange: gather every member's
    [K, ...] value payload (optionally quantile-coded) and segment-merge
    the duplicates through the shared ``inv``
    (``sparse_kernels.merge_rows``).

    ``residual``: optional [vocab, ...] per-member error-feedback table for
    CLIPPED payloads under a FIXED ``compress_range`` (requires ``uids``).
    Dynamic range never clips by construction; a fixed range turns
    out-of-range values into systematic clipping — with EF the clipped
    remainder is carried at the row's table slot and re-enters the next
    encode of that row, so the loss becomes a delayed contribution (the
    same clip-free bound the dense ring's EF mode has; see
    :func:`_ag_exchange_rows`).  Returns ``(merged, new_residual)`` when a
    residual is given, else ``merged``."""
    from lightctr_tpu.ops import sparse_kernels

    use_ef = residual is not None
    all_rows, new_residual = _ag_exchange_rows(
        rows, axis_name, compress_bits=compress_bits,
        compress_range=compress_range, compress_mode=compress_mode,
        uids=uids, residual=residual,
    )
    merged = sparse_kernels.merge_rows(all_rows, inv, num_segments)
    if average:
        merged = merged / n
    if use_ef:
        return merged, new_residual
    return merged


def _sparse_all_reduce_local(
    uids: jax.Array,
    rows: jax.Array,
    axis_name: str,
    n: int,
    average: bool = True,
    compress_bits: int | None = None,
    compress_range: float | str = "dynamic",
    compress_mode: str = "uniform",
    residual: jax.Array | None = None,
):
    """Runs per-device under shard_map: this member's deduped ``uids`` [K]
    (int, padded by repeating id 0) and ``rows`` [K, ...] (summed row
    gradients, zero at padded slots) against every other member's.

    Returns ``(all_uids, merged)`` with shapes [n*K] / [n*K, ...]:
    identical on every member.  ``all_uids`` is the sorted union of the
    members' ids padded by repeating id 0 (``jnp.unique`` fill), and
    ``merged`` holds each unique id's cross-member segment_sum (mean when
    ``average``) in its FIRST slot — later duplicate/padded slots carry
    zero rows, so the pair feeds any ``.add``-based scatter (the
    ``dedup_grads`` convention) or :func:`~lightctr_tpu.embed.table.\
sparse_adagrad_update` directly.

    ``compress_bits``: quantile-code the value payload so 1-2 byte codes
    ride the interconnect instead of fp32 (ids stay int32 — they are the
    cheap part at CTR dims).  Every member encodes through the same
    axis-global table and decode happens receiver-side BEFORE the merge,
    so all members still reconstruct bit-identical merged rows.  Unlike the
    dense ring there is exactly ONE encode per value per step (no per-hop
    accumulation), so error feedback is unnecessary with the default
    dynamic range — the codec noise is single-shot, not compounding.

    ``residual``: [vocab, ...] per-member EF carry for clipped payloads
    under a FIXED ``compress_range`` (see :func:`_ag_merge_rows`); makes
    the return ``(all_uids, merged, new_residual)``.
    """
    _, uniq, inv = _ag_gather_ids(uids, axis_name)
    out = _ag_merge_rows(
        rows, inv, axis_name, n, num_segments=uniq.shape[0],
        average=average, compress_bits=compress_bits,
        compress_range=compress_range, compress_mode=compress_mode,
        uids=uids, residual=residual,
    )
    if residual is not None:
        merged, new_residual = out
        return uniq, merged, new_residual
    return uniq, out


def sparse_all_reduce(
    mesh: Mesh,
    uids: jax.Array,
    rows: jax.Array,
    axis: str = "data",
    average: bool = True,
    compress_bits: int | None = None,
    compress_range: float | str = "dynamic",
    compress_mode: str = "uniform",
    residual: jax.Array | None = None,
):
    """Sparse all-reduce of per-member (ids, row-gradients) pairs.

    ``uids``: [n, K] int ids, one deduped padded slice per mesh member
    (:func:`~lightctr_tpu.embed.table.dedup_grads` shape conventions);
    ``rows``: [n, K, ...] the matching summed row values.  Returns stacked
    ``(all_uids [n, n*K], merged [n, n*K, ...])`` where every member's
    slice is the identical merged union — O(touched) bytes on the wire
    instead of the dense ring's O(vocab) (see
    :func:`prefer_sparse_exchange` for when to switch back, and
    :func:`sparse_reduce_scatter` for the owner-partitioned variant that
    stays O(touched) TOTAL as the world grows).

    ``residual``: optional [n, vocab, ...] per-member error-feedback carry
    for clipped payloads under a FIXED float ``compress_range`` (build the
    zeros with :func:`sparse_ef_residual_init`); the call then returns
    ``(all_uids, merged, new_residual)`` — thread the residual through the
    training loop exactly like the dense ring's EF carry.
    """
    n = mesh.shape[axis]
    use_ef = residual is not None

    def local(u, r, res):
        out = _sparse_all_reduce_local(
            u[0], r[0], axis, n, average=average,
            compress_bits=compress_bits, compress_range=compress_range,
            compress_mode=compress_mode,
            residual=res[0] if use_ef else None,
        )
        if use_ef:
            gu, m, new_res = out
            return gu[None], m[None], new_res[None]
        gu, m = out
        return gu[None], m[None], res

    fn = shard_map(local, mesh=mesh, in_specs=(P(axis), P(axis), P(axis)),
                   out_specs=(P(axis), P(axis), P(axis)))
    res_in = residual if use_ef else jnp.zeros((n, 1), jnp.float32)
    gu, m, new_res = fn(uids, rows, res_in)
    if use_ef:
        return gu, m, new_res
    return gu, m


def sparse_ef_residual_init(mesh: Mesh, table_shape, axis: str = "data"):
    """Zero per-member EF carry for :func:`sparse_all_reduce`'s clipped-
    payload mode: one [vocab, ...] table-keyed residual per mesh member
    (the sparse counterpart of :func:`ef_residual_init`'s padded flat
    vector — keyed by ROW so it survives the batch-to-batch id churn)."""
    n = mesh.shape[axis]
    return jnp.zeros((n,) + tuple(table_shape), jnp.float32)


def rs_owner_partition(uids: jax.Array, n: int, bucket_cap: int):
    """In-jit owner partition plan for one deduped id stream (the modulo
    family of ``dist.partition.ModuloPartition``: owner = uid % n).

    ``uids`` [K] follows the dedup convention (unique ids, padding repeats
    id 0 beyond slot 0 — ``jnp.unique`` fill).  Padded repeats are routed
    NOWHERE (their rows are zero, and dropping them keeps them from eating
    owner 0's bucket capacity).  Returns ``(dest [K], order [K],
    bucket_ids [n, bucket_cap], overflow)``: ``order`` is the
    owner-grouped permutation of the input slots, ``dest`` the flat bucket
    slot of each permuted entry (``n * bucket_cap`` = dropped), so row
    payloads scatter with :func:`rs_scatter_rows` through the SAME plan —
    tables sharing an id stream partition once.  ``overflow`` counts real
    entries that did not fit their destination bucket."""
    k = uids.shape[0]
    owner = (uids % n).astype(jnp.int32)
    is_pad = (uids == 0) & (jnp.arange(k) > 0)
    owner = jnp.where(is_pad, n, owner)
    order = jnp.argsort(owner)  # stable: equal owners keep slot order
    o_sorted = jnp.take(owner, order)
    first = jnp.searchsorted(o_sorted, o_sorted, side="left")
    pos = jnp.arange(k) - first
    over = (pos >= bucket_cap) & (o_sorted < n)
    dest = jnp.where((o_sorted >= n) | over, n * bucket_cap,
                     o_sorted * bucket_cap + pos)
    bucket_ids = jnp.zeros((n * bucket_cap,), uids.dtype).at[dest].set(
        jnp.take(uids, order), mode="drop"
    )
    return (dest, order, bucket_ids.reshape(n, bucket_cap),
            jnp.sum(over.astype(jnp.int32)))


def rs_scatter_rows(
    rows: jax.Array, dest: jax.Array, order: jax.Array, n: int,
    bucket_cap: int, fill=None,
) -> jax.Array:
    """Scatter a [K, ...] row payload into [n, bucket_cap, ...] destination
    buckets through an :func:`rs_owner_partition` plan (empty slots zero —
    the no-op-add convention).  ``fill`` overrides the empty-slot value:
    the folded-EF path scatters CODES and fills with the code of 0.0, so
    the wire bytes equal what encoding zero-filled value buckets
    produced."""
    flat = jnp.take(rows, order, axis=0)
    shape = (n * bucket_cap,) + rows.shape[1:]
    out = (jnp.zeros(shape, rows.dtype) if fill is None
           else jnp.full(shape, fill, rows.dtype))
    out = out.at[dest].set(flat, mode="drop")
    return out.reshape((n, bucket_cap) + rows.shape[1:])


def _rs_ring_exchange(buckets: jax.Array, axis_name: str, n: int):
    """Scatter phase: route bucket d of every member to member d over a
    ``lax.ppermute`` ring — hop i ships exactly ONE [bucket_cap, ...]
    bucket per member (the rotate-by-i permutation of :func:`_ring_perm`'s
    neighbor table), so each member transmits n-1 buckets total.  Returns
    [n, bucket_cap, ...]: slot 0 this member's own contribution, slot i
    the bucket member (idx - i) sent it."""
    idx = jax.lax.axis_index(axis_name)
    parts = [jnp.take(buckets, idx, axis=0)]
    for i in range(1, n):
        perm = [(j, (j + i) % n) for j in range(n)]
        send = jnp.take(buckets, (idx + i) % n, axis=0)
        parts.append(jax.lax.ppermute(send, axis_name, perm))
    return jnp.stack(parts)


def _rs_merge_ids(all_ids: jax.Array, shard_cap: int):
    """Owner-side id merge: the n received [bucket_cap] id buckets ->
    (uniq [shard_cap], inv [n*bucket_cap], overflow).  ``overflow`` counts
    unique ids beyond the shard capacity (0 when :func:`rs_fits` held) —
    read straight off the dedup kernel's distinct count (``jnp.unique``'s
    inverse keeps full ranks under truncation, so no extra sort)."""
    from lightctr_tpu.ops import sparse_kernels

    flat = all_ids.reshape(-1)
    uniq, inv, count = sparse_kernels.dedup_ids(flat, size=shard_cap)
    return uniq, inv, jnp.maximum(0, count - shard_cap)


def _rs_gather_rows(
    rows: jax.Array,
    dest: jax.Array,
    order: jax.Array,
    inv: jax.Array,
    axis_name: str,
    n: int,
    bucket_cap: int,
    shard_cap: int,
    average: bool = True,
    compress_bits: int | None = None,
    compress_range: float | str = "dynamic",
    compress_mode: str = "uniform",
    uids: jax.Array | None = None,
    residual: jax.Array | None = None,
    owner_uids: jax.Array | None = None,
    owner_residual: jax.Array | None = None,
):
    """Row half of the reduce-scatter exchange against a SHARED id plan
    (``dest``/``order`` from :func:`rs_owner_partition`, ``inv`` from
    :func:`_rs_merge_ids`): scatter this table's [K, ...] payload into
    destination buckets, route them over the ppermute ring, merge at the
    owner (``sparse_kernels.merge_rows``), and all-gather
    the merged shards.  Tables sharing one id stream call this once each
    while the id plumbing runs once — the id bytes ride the wire a single
    time per group.

    ``residual``: optional [vocab, ...] per-member EF carry for CLIPPED
    payloads under a FIXED ``compress_range`` (requires ``uids``) — the
    reduce-scatter counterpart of :func:`_ag_exchange_rows`'s carry.  The
    member-side scatter-phase encode is compensated with last step's
    remainder and the fresh clip+quantization error lands back at the
    rows' slots, so clipped mass is delivered late instead of lost; an
    entry dropped by bucket overflow carries its FULL value forward.

    ``owner_residual``: optional [vocab, ...] per-member STAGE-2 carry for
    the owner-side merged-shard encode (requires ``owner_uids`` — the
    owner's merged shard ids from :func:`_rs_merge_ids`).  In ``average``
    mode the merged mean of decoded (range-bounded) values cannot clip,
    so stage 2 adds only sub-bucket rounding noise and the carry is
    rejected as pointless; in SUM mode the owner's merge can reach
    ``n * compress_range`` and the stage-2 encode clips systematically —
    the owner-side carry mirrors the stage-1 member carry (each member
    owns the ``uid % n == idx`` rows, so the per-member [vocab, ...]
    carries partition cleanly and never collide across members).

    Returns ``gathered``, ``(gathered, new_residual)`` when ``residual``
    is given, and ``(gathered, new_residual | None, new_owner_residual)``
    when ``owner_residual`` is."""
    from lightctr_tpu.ops import quantize, sparse_kernels

    use_ef = residual is not None
    use_owner_ef = owner_residual is not None
    new_residual = None
    table = None
    if use_ef or use_owner_ef:
        if compress_bits is None:
            raise ValueError("sparse error feedback needs compress_bits")
        if not isinstance(compress_range, (int, float)):
            raise ValueError(
                "sparse error feedback compensates FIXED-range clipping; "
                "compress_range='dynamic' never clips — pass a float range"
            )
        table = quantize.build_table(
            -compress_range, compress_range,
            bits=compress_bits, mode=compress_mode,
        )
    if use_owner_ef:
        if average:
            raise ValueError(
                "owner_residual is a SUM-mode carry: the averaged merged "
                "shard cannot clip, stage 2 needs no compensation"
            )
        if owner_uids is None:
            raise ValueError("owner-side error feedback needs owner_uids")
    if use_ef:
        if uids is None:
            raise ValueError("sparse error feedback needs uids")
        mask = _ef_valid_mask(uids, rows)
        # folded EF pack (PR 9 follow-up): compensate / encode / decode /
        # carry-scatter run as ONE kernel pass over the ORIGINAL [K, ...]
        # rows, BEFORE the bucket scatter — codes are slot-invariant, so
        # scattering codes ships byte-identical buckets to the old
        # scatter-then-encode order (empty slots carry the code of 0.0,
        # exactly what encoding a zero-filled bucket produced)
        codes_rows, new_residual, dec_rows = \
            sparse_kernels.quantize_pack_ef_update(
                table, rows, uids, residual, mask
            )
        # an entry dropped by bucket overflow must carry its FULL value
        # (its receiver-side reconstruction is 0, not dec): add the
        # kernel's decoded view back at dropped slots — a cheap
        # correction that is exact zero whenever rs_fits held
        kept_flags = jnp.concatenate([
            jnp.ones((n * bucket_cap,), rows.dtype),
            jnp.zeros((1,), rows.dtype),
        ])
        kept = jnp.zeros((uids.shape[0],), rows.dtype).at[order].set(
            jnp.take(kept_flags, dest)
        )
        dropped = (1.0 - kept).reshape((-1,) + (1,) * (rows.ndim - 1))
        new_residual = new_residual.at[uids].add(dec_rows * dropped * mask)
        zero_code = quantize.compress(table, jnp.zeros((), rows.dtype))
        codes = rs_scatter_rows(
            codes_rows, dest, order, n, bucket_cap, fill=zero_code
        )
        all_rows = quantize.extract(
            table, _rs_ring_exchange(codes, axis_name, n)
        )
    else:
        bucket_rows = rs_scatter_rows(rows, dest, order, n, bucket_cap)
        if compress_bits is not None:
            all_rows = _coded_exchange(
                bucket_rows, lambda c: _rs_ring_exchange(c, axis_name, n),
                axis_name, compress_bits, compress_range, compress_mode,
            )
        else:
            all_rows = _rs_ring_exchange(bucket_rows, axis_name, n)
    merged = sparse_kernels.merge_rows(
        all_rows.reshape((n * bucket_cap,) + rows.shape[1:]),
        inv, shard_cap,
    )
    if average:
        merged = merged / n
    if use_owner_ef:
        # stage-2 EF: compensate the owner's merged-shard encode with the
        # previous step's owner carry; encode, decode, fresh error AND the
        # carry scatter at the owned rows' slots run as the one folded
        # kernel pass — the all-gathered codes decode identically on
        # every member
        mask_o = _ef_valid_mask(owner_uids, merged)
        codes_o, new_owner_residual, _ = \
            sparse_kernels.quantize_pack_ef_update(
                table, merged, owner_uids, owner_residual, mask_o
            )
        gathered = quantize.extract(
            table, jax.lax.all_gather(codes_o, axis_name, tiled=True)
        )
        return gathered, new_residual, new_owner_residual
    if compress_bits is not None:
        gathered = _coded_exchange(
            merged,
            lambda c: jax.lax.all_gather(c, axis_name, tiled=True),
            axis_name, compress_bits, compress_range, compress_mode,
        )
    else:
        gathered = jax.lax.all_gather(merged, axis_name, tiled=True)
    if use_ef:
        return gathered, new_residual
    return gathered


def _sparse_reduce_scatter_local(
    uids: jax.Array,
    rows: jax.Array,
    axis_name: str,
    n: int,
    bucket_cap: int,
    shard_cap: int,
    average: bool = True,
    compress_bits: int | None = None,
    compress_range: float | str = "dynamic",
    compress_mode: str = "uniform",
    residual: jax.Array | None = None,
    owner_residual: jax.Array | None = None,
):
    """Per-device body of :func:`sparse_reduce_scatter` (shard_map-inner,
    composable into larger programs — what the hybrid trainer embeds).

    Returns ``(all_uids [n*shard_cap], merged [n*shard_cap, ...],
    overflow)``, identical on every member: the concatenated owner shards.
    Each real id appears exactly once (in its owner's shard) carrying the
    full cross-member merge; the id-0 padding slots of foreign shards
    carry zero rows — the same ``.add``-scatter contract as
    :func:`_sparse_all_reduce_local`.

    ``compress_bits`` codes the row payload of BOTH phases (scatter
    buckets and merged shards) through axis-global tables — two encodes
    per value per step instead of the allgather variant's one, still far
    from the dense ring's per-hop accumulation.

    ``residual``: [vocab, ...] per-member stage-1 EF carry for clipped
    fixed-range payloads; ``owner_residual``: [vocab, ...] stage-2
    owner-side carry for SUM-mode exchanges (see :func:`_rs_gather_rows`);
    each appends its new carry to the return tuple (stage-1 first)."""
    dest, order, bucket_ids, over_b = rs_owner_partition(uids, n, bucket_cap)
    all_ids = _rs_ring_exchange(bucket_ids, axis_name, n)
    uniq, inv, over_s = _rs_merge_ids(all_ids, shard_cap)
    out_ids = jax.lax.all_gather(uniq, axis_name, tiled=True)
    out = _rs_gather_rows(
        rows, dest, order, inv, axis_name, n, bucket_cap, shard_cap,
        average=average, compress_bits=compress_bits,
        compress_range=compress_range, compress_mode=compress_mode,
        uids=uids, residual=residual,
        owner_uids=uniq if owner_residual is not None else None,
        owner_residual=owner_residual,
    )
    if owner_residual is not None:
        out_rows, new_residual, new_owner = out
        if residual is not None:
            return out_ids, out_rows, over_b + over_s, new_residual, new_owner
        return out_ids, out_rows, over_b + over_s, new_owner
    if residual is not None:
        out_rows, new_residual = out
        return out_ids, out_rows, over_b + over_s, new_residual
    return out_ids, out, over_b + over_s


def sparse_reduce_scatter(
    mesh: Mesh,
    uids: jax.Array,
    rows: jax.Array,
    axis: str = "data",
    average: bool = True,
    vocab: int | None = None,
    bucket_cap: int | None = None,
    shard_cap: int | None = None,
    compress_bits: int | None = None,
    compress_range: float | str = "dynamic",
    compress_mode: str = "uniform",
    residual=None,
    owner_residual=None,
):
    """Owner-partitioned sparse all-reduce — generation 2 of
    :func:`sparse_all_reduce` (SparCML's split allreduce,
    arXiv:1802.08021 §4).

    ``uids`` [n, K] / ``rows`` [n, K, ...] as in :func:`sparse_all_reduce`
    (deduped, id-0 padded).  Each member owner-partitions its pairs by
    ``uid % n`` (the PS modulo partition family), ships only
    destination-owned buckets over a ppermute ring, the owner merges
    duplicates with one segment_sum, and only the merged owner shards ride
    the final all_gather — per-member traffic
    ``(n-1)*(bucket_cap + shard_cap)`` entries instead of the allgather
    variant's ``(n-1)*K``, i.e. O(touched) total and roughly flat in world
    size at fixed density.

    Capacities default to :func:`rs_default_caps` (``vocab`` required
    then).  They are EXPECTED sizes with slack: the returned
    ``overflow [n]`` counts entries/ids that did not fit (0 under
    :func:`rs_fits`); exact callers check host-side first and fall back to
    :func:`sparse_all_reduce`.  Returns ``(all_uids [n, n*shard_cap],
    merged [n, n*shard_cap, ...], overflow [n])``.

    ``residual``: optional [n, vocab, ...] per-member error-feedback
    carry for clipped payloads under a FIXED float ``compress_range``
    (:func:`sparse_ef_residual_init` layout — the PR 7 allgather EF,
    now on the reduce-scatter path; see :func:`_rs_gather_rows` for the
    stage-1/stage-2 contract).  Appends ``new_residual`` to the return.

    ``owner_residual``: optional [n, vocab, ...] per-member STAGE-2
    owner-side carry for SUM-mode (``average=False``) exchanges — the
    merged owner shard can reach ``n * compress_range`` and the stage-2
    encode clips systematically where the mean exchange cannot; the
    owner carry mirrors the stage-1 member carry (same
    :func:`sparse_ef_residual_init` layout; each member only ever
    touches its ``uid % n`` owned rows, so the carries partition
    cleanly).  Appends ``new_owner_residual`` to the return (after
    ``new_residual`` when both are given).
    """
    n = mesh.shape[axis]
    use_ef = residual is not None
    use_owner = owner_residual is not None
    if bucket_cap is None or shard_cap is None:
        if vocab is None:
            raise ValueError(
                "sparse_reduce_scatter needs vocab (to derive default "
                "capacities) or explicit bucket_cap/shard_cap"
            )
        db, ds = rs_default_caps(n, uids.shape[-1], vocab)
        bucket_cap = bucket_cap if bucket_cap is not None else db
        shard_cap = shard_cap if shard_cap is not None else ds

    def local(u, r, res, ores):
        out = _sparse_reduce_scatter_local(
            u[0], r[0], axis, n, bucket_cap, shard_cap, average=average,
            compress_bits=compress_bits, compress_range=compress_range,
            compress_mode=compress_mode,
            residual=res[0] if use_ef else None,
            owner_residual=ores[0] if use_owner else None,
        )
        gu, m, over = out[0], out[1], out[2]
        rest = out[3:]
        new_res = rest[0][None] if use_ef else res
        new_ores = rest[-1][None] if use_owner else ores
        return gu[None], m[None], over[None], new_res, new_ores

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(axis), P(axis), P(axis), P(axis)),
                   out_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)))
    res_in = residual if use_ef else jnp.zeros((n, 1), jnp.float32)
    ores_in = owner_residual if use_owner else jnp.zeros((n, 1), jnp.float32)
    gu, m, over, new_res, new_ores = fn(uids, rows, res_in, ores_in)
    out = (gu, m, over)
    if use_ef:
        out = out + (new_res,)
    if use_owner:
        out = out + (new_ores,)
    return out


def psum_all_reduce(mesh: Mesh, stacked_tree, axis: str = "data", average: bool = True):
    """The production path: XLA's own all-reduce (lowers to the ICI ring).
    One shard_map over the whole pytree so XLA fuses the reductions."""
    n = mesh.shape[axis]

    def local(tree):
        def one(x):
            r = jax.lax.psum(x, axis)
            return r / n if average else r

        return jax.tree_util.tree_map(one, tree)

    shapes = jax.tree_util.tree_map(lambda leaf: leaf.shape, stacked_tree)
    flat_tree = jax.tree_util.tree_map(
        lambda leaf: leaf.reshape((n * int(np.prod(leaf.shape[1:])),))
        if leaf.ndim > 1
        else leaf,
        stacked_tree,
    )
    fn = shard_map(local, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
    out = fn(flat_tree)
    return jax.tree_util.tree_map(
        lambda leaf, shape: leaf.reshape(shape), out, shapes,
        is_leaf=lambda x: isinstance(x, jax.Array),
    )
