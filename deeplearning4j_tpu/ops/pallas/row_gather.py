"""Row gathers for the expert layer's dispatch and combine — Pallas kernels
that move only the rows of the pairs a layer holds.

Mosaic copies no single row out of an array in the TPU's tiled layout (a
slice along a tiled dimension has to be whole tiles: 8 rows of float32, 16 of
bfloat16), and a copy of whole tiles moves 8 to 16 times the bytes. So a row
that is to be fetched by its own asynchronous copy is first *packed*: a row of
``d`` values becomes a slab ``[chunks, 128]`` of 32-bit words on an untiled
leading dimension (bfloat16: word ``j`` holds columns ``j`` and ``j + d / 2``;
``chunks`` rounded up to whole tiles of 8), which a copy fetches whole and
contiguous. ``pack_rows`` writes the slabs of the first ``n_rows`` rows (of
the sum of two arrays, where two gradients of one array arrive apart) and
touches no other; the gather kernels fetch one slab a row, a tile's copies all
in flight and started a grid step before they are awaited (the grid has one
step more than tiles), and unpack a tile in VMEM with strided loads (sublane
``i`` of a load is row ``i``'s chunk), so that what leaves the kernel is in
the layout the grouped products read.

Three implementations, registered over the plain lowerings of ``ops/rows.py``:
``gather_rows``, ``gather_sum_rows`` and ``gather_rows_dot``. A tile that
starts at or past ``n_rows`` issues no copy and writes nothing back (its
output block's index is held at the last live tile's); an index past ``n_rows``
is never read as an address; a pair that is not held is fetched by no copy
and is no term of a sum: nothing of it is read.

The bodies are written for a short trace as well as a short run (a kernel is
traced and lowered at every start of a program, warm compile cache or not,
and ``setup_s`` counts it): loops over chunks and not unrolled code, truncating
division, jitted entry points.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.pallas.interpret import interpret_mode
from deeplearning4j_tpu.ops.registry import register_impl

LANES = 128
UNROLL = 8          # copies a trip of the scalar loops starts or awaits
#: rows a grid step packs, sorted rows a step gathers, tokens a step sums:
#: measured on the v5e (PERF.md, PR 35); each divides the grouped product's 512
PACK_ROWS = 256
GATHER_ROWS = 256
SUM_TOKENS = 32
#: what a call's prefetched scalars (an index a row, a weight a pair) may take
#: of the v5e's 1 MiB of SMEM
SMEM_BUDGET_BYTES = 768 * 1024


def _geometry(d, dtype):
    """(values a word, chunks of 128 words a row, chunks a slab, rows a group):
    a group is the rows one tile of the array's own layout holds."""
    per_word = 4 // jnp.dtype(dtype).itemsize
    chunks = d // (per_word * LANES)
    return per_word, chunks, -(-chunks // 8) * 8, 8 * per_word


def _div(a, b):
    """``a // b`` and ``a % b`` of values that are never negative where they are
    used: the truncating operations, one equation each where Python's floor
    and sign rules trace to ten (a kernel's body is traced and lowered at every
    start of the program, warm compile cache or not)."""
    return jax.lax.div(a, jnp.int32(b))


def _rem(a, b):
    return jax.lax.rem(a, jnp.int32(b))


def _last_live(n, tile):
    """The last tile that starts under ``n`` (tile 0 where ``n`` is 0)."""
    return jnp.maximum(_div(n + tile - 1, tile) - 1, 0)


def _lanes(c):
    """The 128 columns of chunk ``c``, a value of a loop."""
    return pl.ds(pl.multiple_of(c * LANES, LANES), LANES)


def _to_words(refs, rows, per_word, chunks, c):
    """Chunk ``c`` of the rows ``rows`` of a ``[tile, d]`` block as words; of
    two blocks, their sum, rounded to the blocks' type as XLA's add rounds it."""
    def columns(at):
        values = [ref[rows, _lanes(at)] for ref in refs]
        if len(values) == 1:
            return values[0]
        both = values[0].astype(jnp.float32) + values[1].astype(jnp.float32)
        return both.astype(values[0].dtype)

    lo = columns(c)
    if per_word == 1:
        return pltpu.bitcast(lo, jnp.uint32)
    hi = columns(c + chunks)
    # a bfloat16 is the upper half of the float32 of the same value
    lo, hi = (pltpu.bitcast(v.astype(jnp.float32), jnp.uint32) for v in (lo, hi))
    return (lo >> 16) | hi


def _from_words(words, per_word):
    """The float32 values a ``[rows, 128]`` block of words holds: one block for
    float32 rows, the lower and the upper column halves for bfloat16."""
    if per_word == 1:
        return (pltpu.bitcast(words, jnp.float32),)
    return (pltpu.bitcast(words << 16, jnp.float32),
            pltpu.bitcast(words & jnp.uint32(0xFFFF0000), jnp.float32))


def _interpreted(flag):
    """The caller's choice, else the package's one switch."""
    return interpret_mode() if flag is None else flag


#: each entry point is jitted, as jax's grouped products are: the four layers'
#: calls at one shape (forward, recomputed and backward) are traced and lowered
#: once a step's program, where as bare closures they added 23 s to a start
#: from a warm compile cache (PERF.md, PR 35)
_jitted = functools.partial(jax.jit, static_argnames=("tile", "interpret"))


# --------------------------------------------------------------------- packing
@_jitted
def pack_rows(table, n_rows, more=None, *, tile=PACK_ROWS, interpret=None):
    """``[N, slab, 128]`` uint32: the slabs of the first ``n_rows`` rows of
    ``table`` (of ``table + more``, where a second array is given); what the
    others hold is unspecified."""
    N, d = table.shape
    per_word, chunks, slab, group = _geometry(d, table.dtype)
    tables = (table,) if more is None else (table, more)

    def kernel(n_ref, *refs):
        table_refs, out_ref = refs[:-1], refs[-1]

        @pl.when(pl.program_id(0) * tile < n_ref[0])
        def _():
            def pack(at, _):
                g, c = _div(at, chunks), _rem(at, chunks)
                r0 = pl.multiple_of(g * group, group)
                words = _to_words(table_refs, pl.ds(r0, group), per_word, chunks, c)
                for half in range(group // 8):
                    out_ref[pl.ds((r0 + 8 * half) * slab + c, 8, stride=slab), :] = (
                        words[8 * half:8 * half + 8])
                return 0

            jax.lax.fori_loop(0, tile // group * chunks, pack, 0)

    def live(i, n_ref):
        return (jnp.minimum(i, _last_live(n_ref[0], tile)), 0)

    packed = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(N // tile,),
            in_specs=[pl.BlockSpec((tile, d), live)] * len(tables),
            out_specs=pl.BlockSpec((tile * slab, LANES), live)),
        out_shape=jax.ShapeDtypeStruct((N * slab, LANES), jnp.uint32),
        interpret=_interpreted(interpret),
        name="pack_rows",
    )(jnp.reshape(n_rows, (1,)).astype(jnp.int32), *tables)
    return packed.reshape(N, slab, LANES)


# ---------------------------------------------------- sorted rows from a table
def _gather_call(table, index, n_rows, scale, other, *, tile, interpret):
    N, d = table.shape
    R = index.shape[0]
    dtype = table.dtype
    per_word, chunks, slab, group = _geometry(d, dtype)
    packed = pack_rows(table, N, interpret=interpret)
    with_scale, with_dots = scale is not None, other is not None

    def kernel(index_ref, n_ref, *refs):
        refs = iter(refs)
        packed_ref = next(refs)
        scale_ref = next(refs) if with_scale else None
        other_ref = next(refs) if with_dots else None
        out_ref = next(refs)
        dots_ref = next(refs) if with_dots else None
        slabs, sems = refs
        # step i starts tile i's copies and works tile i - 1: one step more than tiles
        i, n = pl.program_id(0), n_ref[0]
        done = i - 1

        def copy(t, r, ix):
            slot = t & 1
            at = pl.multiple_of((slot * tile + r) * slab, 8)
            return pltpu.make_async_copy(packed_ref.at[ix], slabs.at[pl.ds(at, slab)],
                                         sems.at[slot])

        @pl.when(i * tile < n)
        def _():
            def start(trip, _):
                for u in range(UNROLL):
                    r = UNROLL * trip + u
                    row = i * tile + r
                    # a row past n_rows fetches row 0: its index is read as no address
                    copy(i, r, jnp.where(row < n, index_ref[row], 0)).start()
                return 0

            jax.lax.fori_loop(0, tile // UNROLL, start, 0)

        @pl.when((done >= 0) & (done * tile < n))
        def _():
            def wait(trip, _):
                for u in range(UNROLL):
                    copy(done, UNROLL * trip + u, 0).wait()
                return 0

            jax.lax.fori_loop(0, tile // UNROLL, wait, 0)
            base = (done & 1) * tile
            lane = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 1)
            sublane = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 0)

            def column(row_ref, r0):
                """``[8, 1]``: entries ``r0 .. r0 + 7`` of a lane-dense ``[tile / 128, 128]`` block."""
                dense = row_ref[pl.ds(_div(r0, LANES), 1), :]
                picked = jnp.where(lane == _rem(r0, LANES) + sublane,
                                   jnp.broadcast_to(dense, (8, LANES)), 0.0)
                return picked.sum(axis=1, keepdims=True)

            def unpack(g, _):
                r0 = pl.multiple_of(g * group, group)
                halves = range(group // 8)
                scales = [column(scale_ref, r0 + 8 * h) for h in halves] if with_scale else None

                def chunk(c, dots):
                    words = [slabs[pl.ds((base + r0 + 8 * h) * slab + c, 8, stride=slab), :]
                             for h in halves]
                    values = [_from_words(w, per_word) for w in words]
                    for part in range(per_word):
                        cols = _lanes(c + part * chunks)
                        got = [values[h][part] for h in halves]
                        if with_dots:
                            mine = other_ref[pl.ds(r0, group), cols].astype(jnp.float32)
                            dots = tuple(dots[h] + mine[8 * h:8 * h + 8] * got[h] for h in halves)
                        if with_scale:
                            got = [got[h] * scales[h] for h in halves]
                        out_ref[pl.ds(r0, group), cols] = jnp.concatenate(got, axis=0).astype(dtype)
                    return dots

                dots = jax.lax.fori_loop(
                    0, chunks, chunk,
                    tuple(jnp.zeros((8, LANES), jnp.float32) for _ in halves) if with_dots else ())
                if with_dots:
                    for h in halves:
                        r8 = r0 + 8 * h
                        l0, at = _rem(r8, LANES), pl.ds(_div(r8, LANES), 1)
                        total = dots[h].sum(axis=1, keepdims=True)
                        spread = jnp.where(lane == l0 + sublane, jnp.broadcast_to(total, (8, LANES)),
                                           0.0).sum(axis=0, keepdims=True)

                        @pl.when(l0 == 0)
                        def _():
                            dots_ref[at, :] = spread  # noqa: B023

                        @pl.when(l0 != 0)
                        def _():
                            dots_ref[at, :] = dots_ref[at, :] + spread  # noqa: B023
                return 0

            jax.lax.fori_loop(0, tile // group, unpack, 0)

    def live(i, index_ref, n_ref):
        return (jnp.clip(i - 1, 0, _last_live(n_ref[0], tile)), 0)

    def live_row(i, index_ref, n_ref):
        return (*live(i, index_ref, n_ref), 0)

    row_block = pl.BlockSpec((None, tile // LANES, LANES), live_row)
    in_specs, args = [pl.BlockSpec(memory_space=pl.ANY)], [packed]
    if with_scale:
        in_specs.append(row_block)
        args.append(scale.astype(jnp.float32).reshape(R // tile, tile // LANES, LANES))
    if with_dots:
        in_specs.append(pl.BlockSpec((tile, d), live))
        args.append(other)
    out_specs = [pl.BlockSpec((tile, d), live)]
    out_shape = [jax.ShapeDtypeStruct((R, d), dtype)]
    if with_dots:
        out_specs.append(row_block)
        out_shape.append(jax.ShapeDtypeStruct((R // tile, tile // LANES, LANES), jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(R // tile + 1,), in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((2 * tile * slab, LANES), jnp.uint32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=out_shape,
        interpret=_interpreted(interpret),
        name="gather_rows_dot" if with_dots else "gather_rows",
    )(index.astype(jnp.int32), jnp.reshape(n_rows, (1,)).astype(jnp.int32), *args)
    return (out[0], out[1].reshape(R)) if with_dots else out[0]


@_jitted
def gather_rows(table, index, n_rows, scale=None, *, tile=GATHER_ROWS, interpret=None):
    return _gather_call(table, index, n_rows, scale, None, tile=tile, interpret=interpret)


@_jitted
def gather_rows_dot(table, index, n_rows, scale, other, *, tile=GATHER_ROWS, interpret=None):
    return _gather_call(table, index, n_rows, scale, other, tile=tile, interpret=interpret)


# ------------------------------------------------ a token's sum over its slots
@_jitted
def gather_sum_rows(rows, place, n_held, weights=None, more=None, *, tile=SUM_TOKENS,
                    interpret=None):
    d = rows.shape[1]
    T, k = place.shape
    dtype = rows.dtype
    per_word, chunks, slab, group = _geometry(d, dtype)
    packed = pack_rows(rows, n_held, more, interpret=interpret)
    weighted = weights is not None
    pairs = tile * k            # the most a tile of tokens can hold
    token_rows = per_word * slab        # a token's sums, as slabs of float32

    def kernel(place_ref, n_ref, *refs):
        refs = iter(refs)
        weights_ref = next(refs) if weighted else None
        packed_ref, out_ref, slabs, sums, held_pairs, held_rows, starts, sems = refs
        # step i starts tile i's copies and sums tile i - 1: one step more than tiles
        i, n = pl.program_id(0), n_ref[0]
        done = i - 1

        def copy(t, q, ix):
            at = pl.multiple_of(((t & 1) * pairs + q) * slab, 8)
            return pltpu.make_async_copy(packed_ref.at[ix], slabs.at[pl.ds(at, slab)],
                                         sems.at[t & 1])

        def trips(t):
            """Copies are started and awaited ``UNROLL`` a trip: a trip's last few
            may fetch row 0 into places of the buffer no pair has."""
            return _div(starts[t & 1, tile] + UNROLL - 1, UNROLL)

        def sum_token(token, _):
            """A token's held pairs lie side by side: their sum stays in
            registers, in slot order. A pair that is not held was fetched by no
            copy and is no term: nothing of it is read, so nothing needs a select."""
            def add(q, total):
                at = pl.multiple_of(((done & 1) * pairs + q) * slab, 8)
                values = _from_words(slabs[pl.ds(at, slab), :], per_word)
                if weighted:
                    w = weights_ref[done * pairs + held_pairs[done & 1, q]]
                    values = [v * w for v in values]
                return tuple(t + v for t, v in zip(total, values))

            zero = tuple(jnp.zeros((slab, LANES), jnp.float32) for _ in range(per_word))
            total = jax.lax.fori_loop(starts[done & 1, token], starts[done & 1, token + 1],
                                      add, zero)
            for part in range(per_word):
                at = pl.multiple_of((token * per_word + part) * slab, 8)
                sums[pl.ds(at, slab), :] = total[part]
            return 0

        def unpack(at, _):
            g, c = _div(at, chunks), _rem(at, chunks)
            r0 = pl.multiple_of(g * group, group)
            for part in range(per_word):
                got = [sums[pl.ds(((r0 + 8 * h) * per_word + part) * slab + c, 8,
                                  stride=token_rows), :] for h in range(group // 8)]
                out_ref[pl.ds(r0, group), _lanes(c + part * chunks)] = (
                    jnp.concatenate(got, axis=0).astype(dtype))
            return 0

        @pl.when(i < pl.num_programs(0) - 1)
        def _():
            """Note which pairs of tile ``i`` are held and where each token's
            begin, then start a copy for each, side by side in the tile's buffer
            in pair order. The scan is written without a branch (a pair is noted
            where the next held one will be noted again): a branch a pair cost
            30 ns of the scalar core, 2 ms a call (PERF.md, PR 35)."""
            def scan(token, q):
                starts[i & 1, token] = q
                for s in range(k):
                    j = token * k + s
                    ix = place_ref[i * pairs + j]
                    at = jnp.minimum(q, pairs - 1)
                    held_rows[i & 1, at] = ix
                    if weighted:        # which pair, to find its weight by
                        held_pairs[i & 1, at] = j
                    q = q + (ix < n).astype(jnp.int32)
                return q

            count = jax.lax.fori_loop(0, tile, scan, 0)
            starts[i & 1, tile] = count

            def start(trip, _):
                for u in range(UNROLL):
                    q = UNROLL * trip + u
                    copy(i, q, jnp.where(q < count, held_rows[i & 1, q], 0)).start()
                return 0

            jax.lax.fori_loop(0, trips(i), start, 0)

        @pl.when(done >= 0)
        def _():
            def wait(trip, _):
                for u in range(UNROLL):
                    copy(done, UNROLL * trip + u, 0).wait()
                return 0

            jax.lax.fori_loop(0, trips(done), wait, 0)
            jax.lax.fori_loop(0, tile, sum_token, 0)
            jax.lax.fori_loop(0, tile // group * chunks, unpack, 0)

    prefetch = [place.reshape(-1).astype(jnp.int32), jnp.reshape(n_held, (1,)).astype(jnp.int32)]
    if weighted:
        prefetch.append(weights.reshape(-1).astype(jnp.float32))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(T // tile + 1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, d), lambda i, *_: (jnp.maximum(i - 1, 0), 0)),
            scratch_shapes=[pltpu.VMEM((2 * pairs * slab, LANES), jnp.uint32),
                            pltpu.VMEM((tile * token_rows, LANES), jnp.float32),
                            pltpu.SMEM((2, pairs), jnp.int32),
                            pltpu.SMEM((2, pairs), jnp.int32),
                            pltpu.SMEM((2, tile + 1), jnp.int32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((T, d), dtype),
        interpret=_interpreted(interpret),
        name="gather_sum_rows",
    )(*prefetch, packed)


# ----------------------------------------------------------------- registration
def _packs(table):
    """Whole packing tiles of lane-aligned words, in a type the words hold."""
    return (table.ndim == 2 and table.dtype in (jnp.bfloat16, jnp.float32)
            and table.shape[0] % PACK_ROWS == 0
            and table.shape[1] % (LANES * 4 // table.dtype.itemsize) == 0)


def _gathers(table, index, n_rows, scale=None, other=None):
    return (_packs(table) and index.shape[0] % GATHER_ROWS == 0
            and 4 * index.shape[0] <= SMEM_BUDGET_BYTES
            and (other is None or other.dtype == table.dtype))


def _sums(rows, place, n_held, weights=None, more=None):
    return (_packs(rows) and place.shape[0] % SUM_TOKENS == 0
            and (more is None or (more.shape, more.dtype) == (rows.shape, rows.dtype))
            and (4 if weights is None else 8) * place.size <= SMEM_BUDGET_BYTES)


# shape contracts, not heuristics: the kernels cannot work a ragged tile, so
# forcing Pallas does not pass them by
register_impl("gather_rows", platform="pallas", requires=_gathers,
              scope="gather_rows")(gather_rows)
register_impl("gather_rows_dot", platform="pallas", requires=_gathers,
              scope="gather_rows_dot")(gather_rows_dot)
register_impl("gather_sum_rows", platform="pallas", requires=_sums,
              scope="gather_sum_rows")(gather_sum_rows)
