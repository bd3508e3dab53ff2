"""The sparse hot path: what every step pays per embedding table.

Dedup the batch's ids, gather the touched rows, merge duplicate gradient
rows, apply the optimizer to the touched rows, pack a payload for the wire.
Each phase is one function here and one implementation — the one a TPU
runs, and every other backend with it:

  - :func:`dedup_ids` — unique+inverse over an id stream: the exact
    ``jnp.unique(..., size=K, fill_value=0)`` contract — sorted unique
    ids, full-rank inverse (ranks may exceed ``size`` when truncated,
    exactly like ``jnp.unique``), plus the distinct count — from three
    sorts with payloads and one scan, and no K-sized gather or scatter
    (docs/KERNELS.md "The dedup").
  - :func:`merge_rows` — duplicate-id segment merge (``segment_sum``).
  - :func:`merge_apply` — segment-merge + scaled Adagrad apply over the
    touched rows, with the merged sum-of-squares the trainer's health
    gradient norm reads.  It works on the live prefix of the dedup slots
    and not on all K of them (:func:`apply_ladder`, :func:`live_plan`;
    docs/KERNELS.md "The sized apply"), and on a mesh on each row shard's
    own run of them (:func:`shard_plan`, :func:`gather_shards`).
  - :func:`gather_rows` — ``block[idx]``, the read half of the
    device-resident row path (tiered store, serving cache).
  - :func:`quantize_pack` / :func:`quantize_pack_ef` /
    :func:`quantize_pack_ef_update` — quantile-codec payload packing (the
    wire codes of ``ops.quantize``) with the error-feedback residual.

Two of these — ``quantize_pack`` and ``quantize_pack_ef`` — have a second
implementation, a Pallas kernel that won its chip run, and so has
``nn/flash_attention``: the registry below is for them.  A registered
kernel names its XLA form and its Pallas form, and :func:`resolve_impl`
picks from what the code observes: ``pallas`` (compiled Mosaic) on a TPU,
``xla`` everywhere else.  The pick is static: nothing falls from one
implementation to another because a lowering, a compile or a run failed —
that is an error.  Tests reach the interpreter through the Pallas form's
own argument (``KERNELS[name].pallas(..., interpret=True)``).  Every pick
is counted in ``trainer_kernel_path_total{phase,impl}`` (once per trace,
not per step — the pick is static inside jit), which
``tools/metrics_report.py --kernels`` reads.

The AST lint in tests/test_obs.py pins every Pallas call site in the
tree to a registered kernel with a declared XLA form — a direct call that
no CPU run could take cannot land.  The Pallas forms of the other phases
were tried and deleted; docs/KERNELS.md ("Tried, and what the compiler
said") keeps what each cost, so nobody writes them again unknowing.
"""

from __future__ import annotations

import math
from functools import lru_cache, partial
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from lightctr_tpu import obs

#: the phases a registered kernel may declare (the ``phase`` label of
#: ``trainer_kernel_path_total``); metrics_report --kernels groups by these
KERNEL_PHASES = ("pack", "attention")


class KernelDef(NamedTuple):
    name: str
    phase: str            # one of KERNEL_PHASES
    reference: Callable   # the XLA form: what runs off a TPU
    pallas: Callable      # pallas impl; MUST accept interpret=bool kwarg


#: name -> KernelDef.  The single source of truth the lint walks.
KERNELS: Dict[str, KernelDef] = {}


def register_kernel(
    name: str, *, phase: str, reference: Callable, pallas: Callable,
) -> None:
    """Register a Pallas kernel with its XLA form.  Both are mandatory —
    off-TPU the XLA form IS the implementation, so a kernel without one
    could strand tier-1."""
    if phase not in KERNEL_PHASES:
        raise ValueError(f"unknown kernel phase {phase!r}")
    if not callable(reference) or not callable(pallas):
        raise ValueError(f"kernel {name!r} needs callable reference AND pallas")
    KERNELS[name] = KernelDef(
        name=name, phase=phase, reference=reference, pallas=pallas,
    )


def resolve_impl(name: str) -> str:
    """Which implementation of a registered kernel a dispatch runs:
    ``pallas`` (compiled Mosaic) on a TPU, ``xla`` everywhere else."""
    if name not in KERNELS:
        raise KeyError(f"unregistered kernel {name!r}")
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _record(phase: str, impl: str) -> None:
    obs.default_registry().inc(
        obs.labeled("trainer_kernel_path_total", phase=phase, impl=impl)
    )


def _resolve(name: str, impl: Optional[str] = None) -> Callable:
    """The implementation one dispatch runs: the telemetry counter records
    the pick (callers pass ``impl`` when a static per-call rule already
    chose)."""
    kd = KERNELS[name]
    impl = impl or resolve_impl(name)
    _record(kd.phase, impl)
    return kd.reference if impl == "xla" else partial(kd.pallas,
                                                      interpret=False)


def next_pow2(n: int, floor: int = 8) -> int:
    """THE pad policy for kernel-facing dynamic lengths: the next power
    of two >= ``n`` (min ``floor``), so pallas grid counts and jit
    shapes land on a bounded ladder instead of compiling per batch
    size.  Train (sparse_trainer), serve (model/cache), and the tiered
    store's device paths all pad through this one helper."""
    out = floor
    while out < n:
        out *= 2
    return out


# =========================================================================
# (a) dedup: unique + inverse over an id stream
# =========================================================================


def dedup_ids(ids: jax.Array, size: Optional[int] = None):
    """Unique+inverse over one id stream -> ``(uids, inv, count)``: sorted
    unique padded with id 0, full-rank inverse and the distinct count,
    output for output what ``jnp.unique(ids, return_inverse=True,
    size=size, fill_value=0)`` gives.  ``size`` defaults to ``len(ids)``
    (no truncation); with ``size < count`` the unique array truncates
    while ``inv`` keeps the rank among ALL distinct values — identical to
    ``jnp.unique`` (callers like the rs shard merge read the count to
    tally overflow) — so the count is the last rank + 1.

    Three sorts and one scan: no K-sized gather, scatter or scatter-add
    (on a v5e each costs 5-9 sorts of the same K; PERF.md section 5).  No
    sort is stable (a stable sort carries one more operand on a TPU, 0.15
    ms of 0.58 a stream at K = 159,744) and none needs to be: ties are
    either impossible or between elements the outputs cannot tell apart."""
    ids = ids.reshape(-1)
    k = ids.shape[0]
    if size is None:
        size = k
    if k == 0:
        return (jnp.zeros((size,), ids.dtype), jnp.zeros((0,), jnp.int32),
                jnp.zeros((), jnp.int32))
    # the sorted ids come out of the sort as an operand, not by ids[perm];
    # equal ids get the same rank, so their order in perm moves nothing
    s, perm = jax.lax.sort((ids, jax.lax.iota(jnp.int32, k)), num_keys=1,
                           is_stable=False)
    first = jnp.concatenate([jnp.ones((1,), jnp.bool_), s[1:] != s[:-1]])
    rank = jnp.cumsum(first.astype(jnp.int32)) - 1
    count = rank[-1] + 1
    # ranks back in the order of the positions (zeros.at[perm].set(rank));
    # perm is a permutation: no ties
    _, inv = jax.lax.sort((perm, rank), num_keys=1, is_stable=False)
    # first occurrences ahead of the repeats, ascending: two keys and no
    # sentinel, so an id of any value and either width sorts right; only
    # repeats of one id tie
    _, packed = jax.lax.sort((~first, s), num_keys=2, is_stable=False)
    n = min(size, k)
    u = jnp.where(jax.lax.iota(jnp.int32, n) < count, packed[:n], 0)
    return jnp.pad(u, (0, size - n)), inv, count


# =========================================================================
# (b) segment merge + merge-apply
# =========================================================================


def merge_rows(rows: jax.Array, inv: jax.Array, num_segments: int):
    """Duplicate-slot segment merge — ``segment_sum(rows, inv,
    num_segments)`` with the dedup convention's drop semantics for
    out-of-range segments."""
    if rows.shape[0] == 0:
        return jnp.zeros((num_segments,) + rows.shape[1:], rows.dtype)
    return jax.ops.segment_sum(rows, inv, num_segments=num_segments)


#: the live-prefix ladder (:func:`apply_ladder`): rungs in sixteenths of the
#: slot count K, closer together at the low end where a Criteo-shape batch
#: lands (28% of its slots distinct); at most 8 rungs, so a table's apply
#: compiles 2 x 9 small branches
_LADDER_SIXTEENTHS = (2, 3, 4, 5, 6, 8, 12, 16)
#: under this many slots the ladder is the one rung K: the tiered store's
#: few hundred slots and a test's few dozen gain nothing from a switch
LADDER_MIN_SLOTS = 8192


@lru_cache(maxsize=None)
def apply_ladder(k: int) -> Tuple[int, ...]:
    """The slot counts the sized apply may take at ``k`` dedup slots,
    ascending, the last one ``k``: a pure function of ``k``, so the host
    (``trainer_apply_slots_total``) and the device name the same rung."""
    if k < LADDER_MIN_SLOTS:
        return (k,)
    # multiples of 128: whole lane tiles of the index vector
    return tuple(sorted({min(k, -(-k * r // (16 * 128)) * 128)
                         for r in _LADDER_SIXTEENTHS}))


def ladder_slots(k: int, count: int) -> int:
    """Slots of the rung the apply takes for ``count`` live slots of ``k``:
    the smallest rung that holds them."""
    return next(s for s in apply_ladder(k) if s >= count)


def _slide(x: jax.Array, start, back: bool = False) -> jax.Array:
    """``x`` slid ``start`` slots along axis 0, zeros coming in: ``out[j]
    = x[start + j]``, or ``x[j - start]`` with ``back``.  A pad and a
    dynamic slice (``start`` may be traced, in ``[0, K]``)."""
    k = x.shape[0]
    wide = jnp.pad(x, ((k, 0) if back else (0, k),)
                   + ((0, 0),) * (x.ndim - 1))
    return jax.lax.dynamic_slice_in_dim(wide, k - start if back else start, k)


def shard_plan(uids: jax.Array, rows: int, lo=None):
    """``(idx, branch, start, count)`` for the table rows ``[lo, lo +
    rows)`` of one dedup-convention id vector ``uids`` [K]; ``lo=None`` is
    the whole table (``start`` is then the int 0 and nothing is slid).

    A slot is live when it is slot 0 or its id is not 0, and a shard's own
    when its id is also in the shard's range.  ``uids`` come sorted, so the
    own slots are one run ``[start, start + count)`` of the live prefix;
    the plan brings that run to slot 0: ``idx[j]`` is the LOCAL row
    ``uids[start + j] - lo`` for ``j < count`` and past the local table
    behind it (``rows + start + j``: ascending, so a scatter with
    ``mode="drop"`` skips it and a sorted, unique run stays sorted and
    unique over any rung).  The caller slides what it pairs with ``idx`` by
    the same ``start``.  ``branch`` indexes ``apply_ladder(K)`` by the
    smallest rung holding ``count`` — or is ``len(ladder)``, the undeclared
    full-K branch, when ``idx`` is not strictly ascending (the
    reduce-scatter exchange hands per-owner sorted segments with pads
    between them; own slots that are not one ascending run read so too).
    Then nothing is slid: ``start`` is 0 and ``idx`` names every own slot
    where it stands.  Sortedness is observed, never assumed."""
    k = uids.shape[0]
    # int64 id streams (the billion-row regime) keep their width
    slot = jnp.arange(k, dtype=jnp.promote_types(uids.dtype, jnp.int32))
    live = (uids != 0) | (slot == 0)
    ids = uids.astype(slot.dtype)
    if lo is None:
        start = 0
        idx = jnp.where(live, ids, rows + slot)
        count = jnp.max(jnp.where(live, slot + 1, 0))
        ascending = jnp.all(idx[1:] > idx[:-1])
    else:
        local = ids - jnp.asarray(lo, slot.dtype)
        own = live & (local >= 0) & (local < rows)
        start = jnp.sum(live & (local < 0), dtype=jnp.int32)
        count = jnp.sum(own, dtype=jnp.int32)
        idx = jnp.where(own, local, rows + slot)
        # the run at slot 0; the pads that come in behind slot K go on
        # ascending past the table.  Ascending with every own slot in it:
        # the run it was taken for
        run = jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([idx, rows + k + slot]), start, k)
        ascending = (jnp.all(run[1:] > run[:-1])
                     & (jnp.sum(run < rows, dtype=jnp.int32) == count))
        idx = jnp.where(ascending, run, idx)
        start = jnp.where(ascending, start, 0)
    ladder = apply_ladder(k)
    rung = jnp.sum(count > jnp.asarray(ladder, jnp.int32))
    branch = jnp.where(ascending, rung, len(ladder)).astype(jnp.int32)
    return idx, branch, start, count


def live_plan(uids: jax.Array, vocab: int):
    """``(idx, branch)`` of :func:`shard_plan` for a whole table of
    ``vocab`` rows: ``idx`` is ``uids`` with every pad slot sent past the
    table (``vocab + slot``), ``branch`` the smallest rung of
    ``apply_ladder(K)`` that holds the live prefix (one pass over K
    int32), or the undeclared branch for ids seen out of order."""
    return shard_plan(uids, vocab)[:2]


def _ladder_branches(k: int, rung: Callable) -> list:
    """``rung(slots, ordered)`` for every rung of ``apply_ladder(k)``,
    then the undeclared full-K branch ``live_plan`` names for ids it saw
    out of order."""
    return [rung(s, True) for s in apply_ladder(k)] + [rung(k, False)]


def gather_live(block: jax.Array, idx: jax.Array, branch: jax.Array,
                zero_pads: bool = False):
    """``block[idx]`` over the rung ``branch`` names, zero rows behind it
    ([K, ...] whatever the rung).  ``block`` is used once in each branch,
    which is what lets XLA keep a donated table in place around the
    switch (docs/KERNELS.md, "Reading tools/aot_step.py").  A pad slot
    inside the rung reads the table's last row (clip) — no caller reads a
    pad slot's row — unless ``zero_pads`` asks for zeros there too."""
    k = idx.shape[0]
    mode = dict(mode="fill", fill_value=0) if zero_pads else dict(mode="clip")

    def rung(s, ordered):
        def f(block, idx):
            rows = jnp.take(block, idx[:s], axis=0,
                            indices_are_sorted=ordered, **mode)
            return jnp.pad(rows, ((0, k - s),) + ((0, 0),) * (rows.ndim - 1))
        return f

    return jax.lax.switch(branch, _ladder_branches(k, rung), block, idx)


def gather_shards(block: jax.Array, uids: jax.Array, axis_name: str):
    """``table[uids]`` in ``uids`` order ([K, ...], zero rows behind the
    live prefix) where ``block`` is this device's shard of the table's
    rows along the mapped axis ``axis_name``: call it inside a
    ``shard_map`` with ``uids`` replicated.  Each shard gathers its own
    run of the slots on its own rung (:func:`shard_plan`; that switch
    holds no collective) with zeros wherever a slot is not its own —
    the shards' rows are summed next, and clip's last row must not leak
    into the sum.  The join is a second switch, over the rung the WHOLE
    live prefix takes — its index comes from the replicated ``uids`` and
    is the same on every device: there the run is slid back to where it
    stands in ``uids`` and one ``psum`` adds the shards."""
    k, rows = uids.shape[0], block.shape[0]
    lo = jax.lax.axis_index(axis_name) * rows
    idx, branch, start, _ = shard_plan(uids, rows, lo)
    part = gather_live(block, idx, branch, zero_pads=True)
    tail = block.shape[1:]
    width = math.prod(tail)

    def rung(s, ordered):
        del ordered

        def f(part, start):
            # every run ends inside the whole prefix's rung, so the slide
            # and the sum are made at s, not at K — and flat, up to the
            # zeros behind the rung: XLA:TPU all-reduces an [s, 32]
            # operand row-major with its 32 lanes padded to 128, four
            # times the bytes, and moves a reshape that only wraps the
            # psum out of the way (docs/KERNELS.md, "On a mesh")
            own = _slide(part[:s].reshape(-1), start * width, back=True)
            joined = jax.lax.psum(own, axis_name)
            return jnp.pad(joined, (0, (k - s) * width)).reshape((k,) + tail)
        return f

    _, whole = live_plan(uids, rows * jax.lax.axis_size(axis_name))
    return jax.lax.switch(whole, _ladder_branches(k, rung), part, start)


def _scatter_live(table, accum, idx, delta, acc, branch):
    """``table[idx] += delta ; accum[idx] = acc`` over the rung ``branch``
    names.  Every live index is distinct and every pad is past the table
    and dropped, so each scatter says ``unique_indices``; the rungs also
    say ``indices_are_sorted`` (``live_plan`` saw it), which spares the
    sort XLA otherwise puts before a scatter."""
    k = idx.shape[0]

    def rung(s, ordered):
        def f(table, accum, idx, delta, acc):
            kw = dict(mode="drop", indices_are_sorted=ordered,
                      unique_indices=True)
            return (table.at[idx[:s]].add(delta[:s], **kw),
                    accum.at[idx[:s]].set(acc[:s], **kw))
        return f

    return jax.lax.switch(branch, _ladder_branches(k, rung),
                          table, accum, idx, delta, acc)


def merge_apply(
    table: jax.Array,
    accum: jax.Array,
    uids: jax.Array,
    rows: jax.Array,
    inv: Optional[jax.Array] = None,
    *,
    lr: float,
    eps: float = 1e-7,
    denom: float = 1.0,
    shard_axis: Optional[str] = None,
):
    """Segment-merge + scaled Adagrad apply over the touched rows of
    ``table``/``accum``.

    ``uids`` [S] follow the dedup convention (sorted unique, padding
    repeats id 0); ``rows`` is either the pre-merge [M, ...] gradient
    payload with its ``inv`` [M] segment map, or — ``inv=None`` — already
    per-uid rows [S, ...] (the reduce-scatter path, whose merge happened
    owner-side mid-exchange).  ``denom`` scales the merged rows
    (``merged / denom`` — the exchange's mean) before the apply.

    Segment-merge (when ``inv`` is given), scale, health sum-of-squares,
    then ``embed.table.sparse_adagrad_update``'s arithmetic — ``acc =
    a[u] + g^2 ; w[u] -= lr g rsqrt(acc + eps) ; a[u] = acc`` — over the
    live prefix of ``uids`` and not over all K slots: the accumulator
    rows come from one switch over the ladder (:func:`gather_live`), the
    arithmetic runs at K, one more switch scatters into table and
    accumulator (:func:`_scatter_live`).  The ids are trusted to be
    unique, as the contract states them, so nothing is deduplicated a
    second time.

    ``shard_axis`` names the mapped mesh axis the table's rows are sharded
    over, for a call inside a ``shard_map``: ``table`` / ``accum`` are
    then this device's rows ``[i * V_e, (i + 1) * V_e)``, ``uids`` and
    ``rows`` the replicated global ones.  The plan is then the shard's own
    (:func:`shard_plan`), the gradient rows are slid by its ``start`` to
    pair with it, and the same arithmetic runs on the shard's run of the
    slots over the shard's rung (docs/KERNELS.md, "On a mesh").

    Returns ``(table', accum', sumsq)``; ``sumsq`` is the merged rows'
    sum of squares (the health gradient-norm contribution), the whole
    payload's on a shard too.  The trajectory matches the chain
    ``segment_sum -> /denom -> sparse_adagrad_update`` to the last
    FMA-contraction ulp; ``sumsq`` may differ in final-ulp accumulation
    order.

    Padded id-0 slots are ZERO-GRADIENT BY CONTRACT, and for ``inv=None``
    payloads the mask below enforces it: the coded reduce-scatter exchange
    leaves decoded dump-slot noise (half-bucket midpoints) in foreign
    shards' id-0 slots, and slot 0 may be the real row 0 — the enforced
    zero keeps codec noise off it.  Merged ``inv`` payloads need no mask:
    pad segments are never referenced, their sums are exactly zero."""
    k = uids.shape[0]
    if inv is None:
        valid = ~((uids == 0) & (jnp.arange(k) > 0))
        rows = rows * valid.astype(rows.dtype).reshape(
            (-1,) + (1,) * (rows.ndim - 1)
        )
        merged = rows
    else:
        merged = jax.ops.segment_sum(rows, inv, num_segments=k)
    if denom != 1.0:
        merged = merged / denom
    sumsq = jnp.sum(merged * merged)
    g = merged.reshape((k,) + table.shape[1:]).astype(table.dtype)
    if shard_axis is None:
        idx, branch = live_plan(uids, table.shape[0])
    else:
        lo = jax.lax.axis_index(shard_axis) * table.shape[0]
        idx, branch, start, _ = shard_plan(uids, table.shape[0], lo)
        # rows behind the run are other shards' gradients: their slots
        # are past the table, and the scatters drop them
        g = _slide(g, start)
    acc = gather_live(accum, idx, branch) + g * g
    delta = -lr * g * jax.lax.rsqrt(acc + eps)
    new_table, new_accum = _scatter_live(table, accum, idx, delta, acc, branch)
    return new_table, new_accum, sumsq


# =========================================================================
# (b2) row gather: the device-resident row path's read half
# =========================================================================


def gather_rows(block: jax.Array, idx: jax.Array):
    """``block[idx]`` row gather — the gather every consumer of a
    device-resident row block runs: the tiered store's hot-tier pulls,
    the trainer's hot-resident fast path, and the serving cache's
    device-block hits (ISSUE 15: train and serve share ONE row path
    through this entry).  Indices MUST be in range; out-of-range ones
    clip rather than trap."""
    idx = idx.reshape(-1)
    if idx.shape[0] == 0:
        return jnp.zeros((0,) + block.shape[1:], block.dtype)
    # mode="clip" explicitly: jnp.take's DEFAULT out-of-range mode is
    # "fill" (NaN rows)
    return jnp.take(block, idx, axis=0, mode="clip")


# =========================================================================
# (c) quantize-on-the-fly payload packing (+ folded EF residual)
# =========================================================================


def _qp_reference(table, x: jax.Array):
    from lightctr_tpu.ops import quantize

    return quantize.compress(table, x)


def _qp_kernel(bnd_ref, x_ref, codes_ref, *, nbp, bc, code_bits):
    """Compare-count encode: ``searchsorted(boundaries, x, side='left')``
    == the number of boundaries strictly below x — a chunked broadcast
    compare-accumulate, bit-identical to the codec's binary search."""
    x = x_ref[...]                                         # [bp, 1]

    def body(c, acc):
        bb = bnd_ref[0, pl.ds(c * bc, bc)]                 # [bc]
        return acc + jnp.sum((x > bb).astype(jnp.int32), axis=1,
                             keepdims=True)

    acc = jax.lax.fori_loop(0, nbp // bc, body,
                            jnp.zeros(x.shape, jnp.int32))
    codes_ref[...] = acc.astype(codes_ref.dtype)


def _qp_flatten(table, x):
    """(boundaries [1, NBp] +inf-padded, flat [P, 1], chunk, code dtype)."""
    nb = int(table.boundaries.shape[0])
    bc = min(256, max(8, nb))
    nbp = -(-nb // bc) * bc
    bnd = table.boundaries.astype(jnp.float32)
    if nbp != nb:
        bnd = jnp.pad(bnd, (0, nbp - nb), constant_values=jnp.inf)
    dtype = jnp.uint8 if table.bits <= 8 else jnp.uint16
    flat = x.reshape(-1, 1).astype(jnp.float32)
    return bnd.reshape(1, nbp), flat, bc, nbp, dtype


def _qp_pallas(table, x: jax.Array, *, interpret: bool):
    bnd, flat, bc, nbp, dtype = _qp_flatten(table, x)
    p = flat.shape[0]
    bp = min(1024, max(8, p))
    pp = -(-p // bp) * bp
    if pp != p:
        flat = jnp.pad(flat, ((0, pp - p), (0, 0)))
    kernel = partial(_qp_kernel, nbp=nbp, bc=bc, code_bits=table.bits)
    codes = pl.pallas_call(
        kernel,
        grid=(pp // bp,),
        out_shape=jax.ShapeDtypeStruct((pp, 1), dtype),
        in_specs=[
            pl.BlockSpec((1, nbp), lambda i: (0, 0)),
            pl.BlockSpec((bp, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bp, 1), lambda i: (i, 0)),
        interpret=interpret,
    )(bnd, flat)
    return codes[:p, 0].reshape(x.shape)


def _wide_codes_impl(table) -> Optional[str]:
    """Codes wider than 8 bits keep the XLA form's binary search on every
    backend: the compare-count sweep the pack kernels run would pay 2^bits
    compares per element (and a 2^bits one-hot decode)."""
    return "xla" if table.bits > 8 else None


def quantize_pack(table, x: jax.Array) -> jax.Array:
    """Dispatch: float payload -> quantile codes, bit-identical to
    ``ops.quantize.compress`` (the wire pack every coded collective hop
    ships).  Codes up to 8 bits — the 4-bit sub-byte tables included —
    ride the compare-count sweep on a TPU; wider tables take the XLA form
    (:func:`_wide_codes_impl`)."""
    fn = _resolve("quantize_pack", impl=_wide_codes_impl(table))
    return fn(table, x)


def quantize_pack_packed(table, x: jax.Array) -> jax.Array:
    """:func:`quantize_pack` plus the sub-byte WIRE form: 4-bit-and-under
    tables bit-pack two codes per byte (``ops.quantize.pack_nibbles`` —
    the ``wire_bits=4`` codec `dist.collectives._wire_row_bytes` prices);
    wider tables return their codes unchanged.  Receiver side:
    ``unpack_nibbles(packed, x.size)`` then ``quantize.extract`` —
    bit-parity with the unpacked reference codec is the contract
    (tests/test_sparse_kernels.py)."""
    codes = quantize_pack(table, x)
    if table.bits <= 4:
        from lightctr_tpu.ops.quantize import pack_nibbles

        return pack_nibbles(codes)
    return codes


def _qp_ef_reference(table, rows, carried, mask):
    """The `_ag_merge_rows` EF encode sequence: compensate with last
    step's carry, encode, decode, fresh error — exactly the chain the
    fused kernel runs in one pass."""
    from lightctr_tpu.ops import quantize

    val = rows + carried * mask
    codes = quantize.compress(table, val)
    dec = quantize.extract(table, codes)
    return codes, (val - dec - carried) * mask


def _qp_ef_kernel(bnd_ref, val_ref, rows_ref, car_ref, mask_ref,
                  codes_ref, delta_ref, *, nbp, bc, nvp, vc):
    """One pass over the payload: val = rows + carried*mask; encode
    (compare-count); decode (chunked one-hot masked sum — exact: every
    non-selected term contributes a signed zero); fresh EF error."""
    rows = rows_ref[...]
    car = car_ref[...]
    m = mask_ref[...]
    val = rows + car * m

    def cbody(c, acc):
        bb = bnd_ref[0, pl.ds(c * bc, bc)]
        return acc + jnp.sum((val > bb).astype(jnp.int32), axis=1,
                             keepdims=True)

    codes = jax.lax.fori_loop(0, nbp // bc, cbody,
                              jnp.zeros(val.shape, jnp.int32))

    def dbody(c, dec):
        vv = val_ref[0, pl.ds(c * vc, vc)]                 # [vc]
        idx = c * vc + jax.lax.broadcasted_iota(
            jnp.int32, (codes.shape[0], vc), 1
        )
        sel = (codes == idx).astype(jnp.float32)
        return dec + jnp.sum(vv * sel, axis=1, keepdims=True)

    dec = jax.lax.fori_loop(0, nvp // vc, dbody,
                            jnp.zeros(val.shape, jnp.float32))
    codes_ref[...] = codes.astype(codes_ref.dtype)
    delta_ref[...] = (val - dec - car) * m


def _qp_ef_pallas(table, rows, carried, mask, *, interpret: bool):
    bnd, flat, bc, nbp, dtype = _qp_flatten(table, rows)
    nv = int(table.values.shape[0])
    vc = min(256, max(8, nv))
    nvp = -(-nv // vc) * vc
    vals = table.values.astype(jnp.float32)
    if nvp != nv:
        vals = jnp.pad(vals, (0, nvp - nv))
    car = carried.reshape(-1, 1).astype(jnp.float32)
    msk = jnp.broadcast_to(mask, rows.shape).reshape(-1, 1).astype(
        jnp.float32
    )
    p = flat.shape[0]
    bp = min(1024, max(8, p))
    pp = -(-p // bp) * bp
    if pp != p:
        flat = jnp.pad(flat, ((0, pp - p), (0, 0)))
        car = jnp.pad(car, ((0, pp - p), (0, 0)))
        msk = jnp.pad(msk, ((0, pp - p), (0, 0)))
    codes, delta = pl.pallas_call(
        partial(_qp_ef_kernel, nbp=nbp, bc=bc, nvp=nvp, vc=vc),
        grid=(pp // bp,),
        out_shape=(
            jax.ShapeDtypeStruct((pp, 1), dtype),
            jax.ShapeDtypeStruct((pp, 1), jnp.float32),
        ),
        in_specs=[
            pl.BlockSpec((1, nbp), lambda i: (0, 0)),
            pl.BlockSpec((1, nvp), lambda i: (0, 0)),
            pl.BlockSpec((bp, 1), lambda i: (i, 0)),
            pl.BlockSpec((bp, 1), lambda i: (i, 0)),
            pl.BlockSpec((bp, 1), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((bp, 1), lambda i: (i, 0)),
            pl.BlockSpec((bp, 1), lambda i: (i, 0)),
        ),
        interpret=interpret,
    )(bnd, vals.reshape(1, nvp), flat, car, msk)
    return (codes[:p, 0].reshape(rows.shape),
            delta[:p, 0].reshape(rows.shape))


def quantize_pack_ef(table, rows: jax.Array, carried: jax.Array,
                     mask: jax.Array):
    """Dispatch: EF-folded payload pack -> ``(codes, delta)`` where
    ``val = rows + carried*mask``, ``codes = compress(val)`` and
    ``delta = (val - extract(codes) - carried) * mask`` — the fresh
    error-feedback contribution the caller scatters back at the rows'
    table slots.  One traversal instead of the reference's
    compensate/encode/decode/error chain.  8-bit-and-under codes take
    the Pallas path (see :func:`quantize_pack`)."""
    fn = _resolve("quantize_pack_ef", impl=_wide_codes_impl(table))
    return fn(table, rows, carried, mask)


def quantize_pack_ef_update(table, rows: jax.Array, uids: jax.Array,
                            residual: jax.Array, mask: jax.Array):
    """EF pack with the residual carry update -> ``(codes, new_residual,
    dec)`` — ``dec`` is the receiver-side decoded view, computed on the
    way anyway and returned so callers that need it (the rs overflow-drop
    correction) pay no second ``extract``.  ``rows`` [S, ...] follow the
    dedup convention with ``uids`` [S] naming their table slots;
    ``residual`` is the [vocab, ...] table-keyed carry and ``mask`` the
    validity mask over slots (pads must neither read nor write the
    carry).  Computes ``val = rows + residual[uids]*mask``, the codes,
    the decode, and ``residual[uids] += (val - dec - carried) * mask``.
    ``uids``/``mask`` MUST honor the dedup convention — at most one
    UNMASKED slot per uid."""
    if rows.shape[0] == 0:
        dtype = jnp.uint8 if table.bits <= 8 else jnp.uint16
        return (jnp.zeros(rows.shape, dtype), residual,
                jnp.zeros(rows.shape, jnp.float32))
    from lightctr_tpu.ops import quantize

    carried = jnp.take(residual, uids, axis=0)
    val = rows + carried * mask
    codes = quantize.compress(table, val)
    dec = quantize.extract(table, codes)
    new_residual = residual.at[uids].add((val - dec - carried) * mask)
    return codes, new_residual, dec


register_kernel("quantize_pack", phase="pack",
                reference=_qp_reference, pallas=_qp_pallas)
register_kernel("quantize_pack_ef", phase="pack",
                reference=_qp_ef_reference, pallas=_qp_ef_pallas)
