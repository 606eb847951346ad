"""The gated delta rule as a pair of Pallas kernels: ``delta_rule_fwd`` and
``delta_rule_bwd``, registered over ``op("gated_delta_rule")``'s plain lowering
(``ops/delta_rule.py``, whose text states the recurrence and the chunk algebra
this follows to the letter).

**Grid.** ``(B, H, chunks / CHUNKS)``: a grid step works one head over
``CHUNKS`` chunks of ``CHUNK`` tokens. The chunk axis is the last and sequential
(``"arbitrary"``): each head's ``[K, V]`` float32 state lives in a VMEM
scratch for the whole walk, from ``initial_state`` (or zeros) to the final
state. The backward walks the same grid with the chunk axis reversed by its
index maps, a head's ``dS`` (float32 ``[K, V]``) in VMEM scratch, seeded with
the final state's cotangent.

**A chunk, in VMEM** (one head): the running sum ``G`` of the log-decay; the
decayed scores ``A`` (keys on keys, below the diagonal) and ``B`` (queries on
keys, on and below it) by sub-chunks of ``SUB`` with ``_decayed_scores``'
reference points (a diagonal block around its sub-chunk's middle token, a
block below it around the first token of its rows' sub-chunk, a select above a
block's diagonal); the inverse ``T`` of the unit lower-triangular system
``I + Diag(beta) A`` in float32, doing only its non-zero work: its diagonal
blocks of ``BLOCK`` tokens by substitution on the vector unit, then merged to
32 and 64 by ``T <- T - T N T`` (``N`` the system's part between the two
halves of each new block) over the second halves' rows alone, the only ones
it fills (no power series, whose terms would cancel); ``W = T Diag(beta) (K
* exp(G))``, ``U0 = T Diag(beta) V``; then ``U = U0 - W S``, ``O = (Q *
exp(G)) S + B U`` and the next state. The forward writes ``o``, the final state
and each chunk's starting state in ``k``'s type (what the plain lowering keeps;
PERF.md). The backward computes a chunk's factors again from its saved
starting state and pulls ``dO`` and ``dS`` through the outputs, the state
update, ``U = U0 - W S``, the inverse (``T^T`` on the right-hand side's
cotangent, ``-T^T d(solved) solved^T`` below the diagonal for the system's)
and the decayed scores, whose reference points carry over: a factor's
gradient is the same split product, and the reference's own terms, which
cancel, are not formed. None of the chunk algebra's float32 factors exists in
HBM; the residuals are the operands and the chunks' starting states.

**Precision**, the plain lowering's: the state, the running sums, the
exponentials, the inverse and every product's sum in float32 (the inverse's
substitution exact float32 arithmetic, its merges' products at ``HIGHEST``),
the other products' operands in ``k``'s type.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.delta_rule import CHUNK, SUB
from deeplearning4j_tpu.ops.pallas.interpret import interpret_mode
from deeplearning4j_tpu.ops.registry import register_impl

#: chunks a grid step, worked by a loop over one chunk's body; measured on the
#: v5e at [1, 32, 8192, 128], forward and backward (PERF.md, PR 37, call 19):
#: 1, 2, 4 and 8 within 3 %, and four heads a grid step 1 % from one
CHUNKS = 4

#: ``checkpoint_name``s of the forward's output and chunk states, which the
#: backward reads: a layer's checkpoint that keeps them (``REMAT_POLICY``,
#: ``nn/layers/base.py``) does not run the forward kernel again under ``remat``
SAVED_OUT, SAVED_STATES = "delta_rule_out", "delta_rule_states"

#: the diagonal blocks of a chunk's inverse solved by substitution before the
#: merges at ``HIGHEST`` (``_unit_lower_inverse``); measured on the v5e in the
#: hybrid decoder's training step (PERF.md): blocks of 8, with a third merge,
#: ran it 1.3-3.9 % slower
BLOCK = 16

_F32 = jnp.float32
_EXACT = jax.lax.Precision.HIGHEST


def _dot(a, b, contract=(1, 0), exact=False):
    """``a`` and ``b`` contracted over ``a``'s axis ``contract[0]`` and ``b``'s
    ``contract[1]``, summed in float32: (1, 0) ``a b``, (1, 1) ``a b^T``, (0,
    0) ``a^T b``. ``exact``: float32 operands at ``HIGHEST`` (the inverse and
    the running sums); else the operands as they come."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        precision=_EXACT if exact else None, preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _column(row):
    """``[1, n]`` -> ``[n, 1]`` (a select on the diagonal and a sum along it)."""
    n = row.shape[-1]
    return jnp.where(_iota((n, n), 0) == _iota((n, n), 1), row, 0.0).sum(axis=1, keepdims=True)


def _row(column):
    """``[n, 1]`` -> ``[1, n]``."""
    n = column.shape[0]
    return jnp.where(_iota((n, n), 0) == _iota((n, n), 1), column, 0.0).sum(axis=0, keepdims=True)


def _running_sum(x, reverse=False):
    """``x`` ``[CHUNK, D]`` float32 summed along the chunk from its start (or,
    ``reverse``, from its end) to each token."""
    j, i = _iota((CHUNK, CHUNK), 0), _iota((CHUNK, CHUNK), 1)
    return _dot(jnp.where(i <= j if not reverse else i >= j, 1.0, 0.0), x, exact=True)


def _at_rows(G, offset, size=SUB):
    """``[CHUNK, K]``: each row the row of ``G`` at ``offset`` in its block of
    ``size`` rows (a sub-chunk)."""
    return jnp.concatenate([jnp.broadcast_to(G[a * size + offset:a * size + offset + 1], (size, G.shape[1]))
                            for a in range(CHUNK // size)], axis=0)


def _stacked(t):
    """keys' and queries' rows of one sub-chunk each on top of each other:
    ``[2 CHUNK, ..]`` -> per sub-chunk ``a`` the ``[2 SUB, ..]`` of its keys'
    then its queries' rows."""
    return [jnp.concatenate([t[a * SUB:(a + 1) * SUB], t[CHUNK + a * SUB:CHUNK + (a + 1) * SUB]])
            for a in range(CHUNK // SUB)]


class _Scores:
    """The decayed scores of one chunk, ``A`` (keys on keys, strictly below
    the diagonal) and ``B`` (queries on keys, on and below it), with the split
    factors the backward takes again: the keys' and queries' rows stacked
    ``[2 CHUNK, K]`` as the left operand, the keys as the right."""

    def __init__(self, q, k, G, dt):
        C, n = CHUNK, CHUNK // SUB
        j, i = _iota((C, C), 0), _iota((C, C), 1)
        self.diagonal = (j // SUB == i // SUB) & (j >= i)
        rows = jnp.concatenate([k, q])
        # the diagonal blocks, around each sub-chunk's middle token: both
        # exponents at most what SUB / 2 tokens decay by
        mid = _at_rows(G, SUB // 2)
        self.e_mid = jnp.exp(G - mid)
        self.r_mid = jnp.exp(mid - G)
        self.left_mid = rows * jnp.concatenate([self.e_mid, self.e_mid])
        self.right_mid = k * self.r_mid
        diag = _dot(self.left_mid.astype(dt), self.right_mid.astype(dt), (1, 1))    # [2C, C]
        # above a block's diagonal both exponents can be positive at once and
        # their product overflow: a select, never a product with 0
        both = jnp.concatenate([self.diagonal, self.diagonal])
        diag = jnp.where(both, diag, 0.0)
        # the blocks below: the rows of sub-chunk a against every earlier
        # token, around the first token of a; the right factor's rows from a's
        # first token on are zeros, and their exponent is never formed
        self.e_first = jnp.exp(G - _at_rows(G, 0))
        self.left_first = rows * jnp.concatenate([self.e_first, self.e_first])
        self.right_below, self.fac_below = [None], [None]
        below = [jnp.zeros((2 * SUB, C), _F32)]
        left_by_sub = _stacked(self.left_first)
        for a in range(1, n):
            fac = jnp.exp(G[a * SUB:a * SUB + 1] - G[:a * SUB])
            fac = jnp.concatenate([fac, jnp.zeros((C - a * SUB, G.shape[1]), _F32)])
            self.fac_below.append(fac)
            self.right_below.append(k * fac)
            below.append(_dot(left_by_sub[a].astype(dt), self.right_below[a].astype(dt), (1, 1)))
        below_k = jnp.concatenate([b[:SUB] for b in below])
        below_q = jnp.concatenate([b[SUB:] for b in below])
        self.A = jnp.where(j > i, diag[:C], 0.0) + below_k
        self.B = diag[C:] + below_q

    def pull(self, dA, dB, dt):
        """The cotangents of ``A`` and ``B`` (zero where they are not
        specified) pulled to ``(dk, dq, dG)``, each ``[CHUNK, K]`` float32."""
        C, n = CHUNK, CHUNK // SUB
        i = _iota((2 * SUB, C), 1)
        d_diag = jnp.where(jnp.concatenate([self.diagonal, self.diagonal]),
                           jnp.concatenate([dA, dB]), 0.0).astype(dt)                   # [2C, C]
        d_left = _dot(d_diag, self.right_mid.astype(dt))                                 # [2C, K]
        d_right = _dot(d_diag, self.left_mid.astype(dt), (0, 0))                         # [C, K]
        dG = (self.left_mid[:C] * d_left[:C] + self.left_mid[C:] * d_left[C:]
              - self.right_mid * d_right)
        dk = self.e_mid * d_left[:C] + self.r_mid * d_right
        dq = self.e_mid * d_left[C:]
        left_by_sub = _stacked(self.left_first)
        dPs = _stacked(jnp.concatenate([dA, dB]))
        d_left_first = [jnp.zeros((2 * SUB, self.right_mid.shape[1]), _F32)]
        for a in range(1, n):
            dP = jnp.where(i < a * SUB, dPs[a], 0.0).astype(dt)                          # [2SUB, C]
            d_left_first.append(_dot(dP, self.right_below[a].astype(dt)))
            d_right = _dot(dP, left_by_sub[a].astype(dt), (0, 0))                        # [C, K]
            dk = dk + self.fac_below[a] * d_right
            dG = dG - self.right_below[a] * d_right
        d_left_k = jnp.concatenate([d[:SUB] for d in d_left_first])
        d_left_q = jnp.concatenate([d[SUB:] for d in d_left_first])
        dk = dk + self.e_first * d_left_k
        dq = dq + self.e_first * d_left_q
        dG = dG + self.left_first[:C] * d_left_k + self.left_first[C:] * d_left_q
        return dk, dq, dG


def _unit_lower_inverse(N):
    """``(I + N)^-1`` for ``N`` ``[CHUNK, CHUNK]`` float32 strictly lower
    triangular, the exact block inverse doing only its non-zero work (the
    layout of ``fla``'s ``solve_tril``). Its diagonal blocks of ``BLOCK``
    tokens by substitution in float32 on the vector unit, all blocks at once:
    step ``c`` takes row ``c`` of each block, final by then, off the rows below
    it (``T -= N[:, pivot] T[pivot]``). Then blocks of ``s`` merged into ``2 s``
    by ``T <- T - T N' T``, ``N'`` the part of ``N`` that links the two halves
    of a new block: ``T N' T`` is zero but on the second halves' rows, so both
    products take those rows alone, at ``HIGHEST``."""
    j, i = _iota(N.shape, 0), _iota(N.shape, 1)
    T = jnp.where(j == i, 1.0, 0.0)
    for c in range(BLOCK - 1):
        below = jnp.where(i == j // BLOCK * BLOCK + c, N, 0.0).sum(axis=1, keepdims=True)
        T = T - below * _at_rows(T, c, BLOCK)
    size = BLOCK
    while size < CHUNK:
        links = jnp.where((j // (2 * size) == i // (2 * size)) & (j // size != i // size), N, 0.0)
        blocks = [T[a:a + size] for a in range(0, CHUNK, size)]
        update = _dot(_dot(jnp.concatenate(blocks[1::2]), links, exact=True), T, exact=True)
        blocks[1::2] = [b - update[n * size:(n + 1) * size] for n, b in enumerate(blocks[1::2])]
        T = jnp.concatenate(blocks)
        size *= 2
    return T


class _Chunk:
    """One head's chunk, everything but the state: ``q``, ``k`` ``[CHUNK, K]``,
    ``v`` ``[CHUNK, V]`` float32, ``g`` ``[CHUNK, K]`` float32, ``beta``
    ``[CHUNK, 1]`` float32; products' operands in ``dt``."""

    def __init__(self, q, k, v, g, beta, dt):
        self.q, self.k, self.v, self.beta, self.dt = q, k, v, beta, dt
        self.G = _running_sum(g)
        self.end = self.G[CHUNK - 1:]                        # [1, K]
        self.E = jnp.exp(self.G)
        self.keep = jnp.exp(self.end)                        # what the chunk leaves of its start
        self.to_end = jnp.exp(self.end - self.G)
        self.k_end = k * self.to_end                         # the keys as the chunk's end sees them
        self.scores = _Scores(q, k, self.G, dt)
        self.T = _unit_lower_inverse(beta * self.scores.A)
        self.W = _dot(self.T, beta * k * self.E, exact=True)
        self.U0 = _dot(self.T, beta * v, exact=True)

    def forward(self, S):
        """``(o [CHUNK, V], the next state)`` from the state ``S`` ``[K, V]``
        float32 the chunk starts from."""
        dt = self.dt
        Sb = S.astype(dt)
        Ub = (self.U0 - _dot(self.W.astype(dt), Sb)).astype(dt)
        o = _dot((self.q * self.E).astype(dt), Sb) + _dot(self.scores.B.astype(dt), Ub)
        return o, _column(self.keep) * S + _dot(self.k_end.astype(dt), Ub, (0, 0))

    def backward(self, S, dO, dS_next):
        """``(dq, dk, dv, dg, dbeta [CHUNK, 1], dS)`` from the starting state
        ``S``, the outputs' cotangent ``dO`` ``[CHUNK, V]`` and the next
        state's ``dS_next`` ``[K, V]``, all float32."""
        dt, C = self.dt, CHUNK
        q, k, v, beta, E, scores = self.q, self.k, self.v, self.beta, self.E, self.scores
        Sb, dOb, dSb = S.astype(dt), dO.astype(dt), dS_next.astype(dt)
        Ub = (self.U0 - _dot(self.W.astype(dt), Sb)).astype(dt)
        j, i = _iota((C, C), 0), _iota((C, C), 1)
        dU = _dot(scores.B.astype(dt), dOb, (0, 0)) + _dot(self.k_end.astype(dt), dSb)   # [C, V]
        dQg = _dot(dOb, Sb, (1, 1))                                                   # [C, K]
        dB = jnp.where(j >= i, _dot(dOb, Ub, (1, 1)), 0.0)                             # [C, C]
        d_k_end = _dot(Ub, dSb, (1, 1))                                               # [C, K]
        d_keep = _row(jnp.sum(S * dS_next, axis=1, keepdims=True))                    # [1, K]
        dUb = dU.astype(dt)
        dW = -_dot(dUb, Sb, (1, 1))                                                   # [C, K]
        dS = (_column(self.keep) * dS_next + _dot((q * E).astype(dt), dOb, (0, 0))
              - _dot(self.W.astype(dt), dUb, (0, 0)))
        # the inverse: T^T on the right-hand side's cotangent, the system's
        # cotangent -T^T d(solved) solved^T below the diagonal
        dRk = _dot(self.T, dW, (0, 0), exact=True)
        dRv = _dot(self.T, dU, (0, 0), exact=True)
        dL = -jnp.where(j > i, _dot(dRk, self.W, (1, 1), exact=True)
                        + _dot(dRv, self.U0, (1, 1), exact=True), 0.0)
        dbeta = (jnp.sum(dL * scores.A, axis=1, keepdims=True)
                 + jnp.sum(k * E * dRk, axis=1, keepdims=True)
                 + jnp.sum(v * dRv, axis=1, keepdims=True))
        dv = beta * dRv
        dk, dq, dG = scores.pull(beta * dL, dB, dt)
        dk = dk + beta * E * dRk + self.to_end * d_k_end
        dq = dq + E * dQg
        dG = (dG + E * (beta * k * dRk + q * dQg) - self.k_end * d_k_end)
        d_end = jnp.sum(self.k_end * d_k_end, axis=0, keepdims=True) + self.keep * d_keep
        dg = _running_sum(dG, reverse=True) + d_end
        return dq, dk, dv, dg, dbeta, dS


def _rows(c):
    return pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)


def _chunk_at(refs, c, dt):
    """The chunk ``c`` of the grid step's blocks."""
    q_ref, k_ref, v_ref, g_ref, b_ref = refs
    rows = _rows(c)
    return _Chunk(q_ref[rows].astype(_F32), k_ref[rows].astype(_F32), v_ref[rows].astype(_F32),
                  g_ref[rows].astype(_F32), _column(b_ref[c].astype(_F32)), dt)


def _each(n, body):
    """``body(i)`` for ``i`` in ``range(n)``, as a loop: one chunk's body in the
    kernel, however many a grid step works (unrolled, each copy is traced and
    lowered at every call of the kernel, seconds of a step's set-up a copy)."""
    jax.lax.fori_loop(0, n, lambda i, _: body(i), None)


def _fwd_kernel(*refs, chunks, given):
    operands, refs = refs[:5], refs[5:]
    s0_ref, refs = (refs[0], refs[1:]) if given else (None, refs)
    o_ref, end_ref, starts_ref, S_scr = refs
    dt = operands[1].dtype
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _start():
        S_scr[...] = s0_ref[...] if given else jnp.zeros(S_scr.shape, _F32)

    def step(c):
        S = S_scr[...]
        starts_ref[c] = S.astype(starts_ref.dtype)
        o, S_scr[...] = _chunk_at(operands, c, dt).forward(S)
        o_ref[_rows(c)] = o.astype(o_ref.dtype)

    _each(chunks, step)

    @pl.when(n == pl.num_programs(2) - 1)
    def _end():
        end_ref[...] = S_scr[...]


def _bwd_kernel(*refs, chunks):
    operands, (starts_ref, do_ref, dend_ref), refs = refs[:5], refs[5:8], refs[8:]
    dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds0_ref, dS_scr = refs
    dt = operands[1].dtype
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _start():
        dS_scr[...] = dend_ref[...]

    def step(c):
        c = chunks - 1 - c                          # the chunks in reverse
        rows = _rows(c)
        dq, dk, dv, dg, dbeta, dS_scr[...] = _chunk_at(operands, c, dt).backward(
            starts_ref[c].astype(_F32), do_ref[rows].astype(_F32), dS_scr[...])
        dq_ref[rows] = dq.astype(dq_ref.dtype)
        dk_ref[rows] = dk.astype(dk_ref.dtype)
        dv_ref[rows] = dv.astype(dv_ref.dtype)
        dg_ref[rows] = dg
        db_ref[c] = _row(dbeta)

    _each(chunks, step)

    @pl.when(n == pl.num_programs(2) - 1)
    def _end():
        ds0_ref[...] = dS_scr[...]


def _specs(B, H, T, K, V, reverse):
    """The grid and the block specs of the operands ``q, k, v, g, beta`` (beta
    as ``[B, H, chunks, 1, CHUNK]``), of a head's ``[K, V]`` and of the
    chunks' states ``[B, H, chunks, K, V]``; a block holds one head, and its
    batch and head axes are squeezed."""
    steps = T // (CHUNK * CHUNKS)
    at = (lambda n: steps - 1 - n) if reverse else (lambda n: n)     # noqa: E731
    rows = lambda D: pl.BlockSpec((None, None, CHUNK * CHUNKS, D),   # noqa: E731
                                  lambda b, h, n: (b, h, at(n), 0))
    by_chunk = lambda D: pl.BlockSpec((None, None, CHUNKS, *D),      # noqa: E731
                                      lambda b, h, n: (b, h, at(n), 0, 0))
    state = pl.BlockSpec((None, None, K, V), lambda b, h, n: (b, h, 0, 0))
    return ((B, H, steps),
            [rows(K), rows(K), rows(V), rows(K), by_chunk((1, CHUNK))], state, by_chunk((K, V)))


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))


def _forward(q, k, v, g, beta, S0):
    """``(o, the final state, the chunks' starting states)``: ``T`` a whole
    number of grid steps' chunks, ``beta`` ``[B, H, chunks, 1, CHUNK]``."""
    B, H, T, K = k.shape
    V = v.shape[-1]
    grid, in_specs, state, starts = _specs(B, H, T, K, V, reverse=False)
    operands = [q, k, v, g, beta]
    if S0 is not None:
        operands.append(S0)
        in_specs = in_specs + [state]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunks=CHUNKS, given=S0 is not None),
        name="delta_rule_fwd",
        out_shape=(jax.ShapeDtypeStruct((B, H, T, V), v.dtype),
                   jax.ShapeDtypeStruct((B, H, K, V), _F32),
                   jax.ShapeDtypeStruct((B, H, T // CHUNK, K, V), k.dtype)),
        grid=grid, in_specs=in_specs,
        out_specs=(in_specs[2], state, starts),
        scratch_shapes=[pltpu.VMEM((K, V), _F32)],
        compiler_params=_params(),
        interpret=interpret_mode(),
    )(*operands)


def _backward(q, k, v, g, beta, starts, dO, dS_end):
    """``(dq, dk, dv, dg, dbeta, d initial state)``, the shapes of ``_forward``'s."""
    B, H, T, K = k.shape
    V = v.shape[-1]
    grid, in_specs, state, by_chunk = _specs(B, H, T, K, V, reverse=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunks=CHUNKS),
        name="delta_rule_bwd",
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype), jax.ShapeDtypeStruct(g.shape, _F32),
                   jax.ShapeDtypeStruct(beta.shape, _F32), jax.ShapeDtypeStruct((B, H, K, V), _F32)),
        grid=grid, in_specs=in_specs + [by_chunk, in_specs[2], state],
        out_specs=(*in_specs, state),
        scratch_shapes=[pltpu.VMEM((K, V), _F32)],
        compiler_params=_params(),
        interpret=interpret_mode(),
    )(q, k, v, g, beta, starts, dO, dS_end)


@jax.custom_vjp
def _kernels(q, k, v, g, beta, S0):
    o, S_end, _ = _forward(q, k, v, g, beta, S0)
    return o, S_end


def _kernels_fwd(q, k, v, g, beta, S0):
    o, S_end, starts = _forward(q, k, v, g, beta, S0)
    o, starts = checkpoint_name(o, SAVED_OUT), checkpoint_name(starts, SAVED_STATES)
    return (o, S_end), (q, k, v, g, beta, starts, S0)


def _kernels_bwd(residuals, cotangents):
    *operands, starts, S0 = residuals
    dO, dS_end = cotangents
    *grads, dS0 = _backward(*operands, starts, dO, dS_end.astype(_F32))
    return (*grads, None if S0 is None else dS0)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def gated_delta_rule(q, k, v, g, beta, *, initial_state=None):
    """``op("gated_delta_rule")`` by the kernel pair, the plain lowering's
    contract: ``T`` padded to whole grid steps here (``g = 0`` and ``beta =
    0`` leave the state as it is), ``g`` and ``beta`` taken in float32."""
    B, H, T, K = k.shape
    pad = -T % (CHUNK * CHUNKS)
    rows = lambda t: jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0)))     # noqa: E731
    beta = jnp.pad(beta.astype(_F32), ((0, 0), (0, 0), (0, pad))).reshape(B, H, -1, 1, CHUNK)
    S0 = None if initial_state is None else initial_state.astype(_F32)
    o, S_end = _kernels(rows(q), rows(k), rows(v), rows(g.astype(_F32)), beta, S0)
    return o[:, :, :T], S_end


def _requires(q, k, v, g, beta, *, initial_state=None):
    # the widths a block tiles whole (lanes of 128), the types the kernels
    # take (products' operands in k's type, bfloat16 or float32)
    return (k.shape[-1] % 128 == 0 and v.shape[-1] % 128 == 0
            and q.dtype == k.dtype == v.dtype and k.dtype in (jnp.bfloat16, jnp.float32))


def _applicable(q, k, v, g, beta, *, initial_state=None):
    # on the chip alone (off it the kernels would run in the interpreter), at
    # every length: MEASURED on the v5e at [1, 32, T, 128] bfloat16, forward
    # and backward, plain / kernels: T 256 3.05 / 1.89 ms, 2,048 15.87 / 6.89,
    # 8,192 54.89 / 24.00 (PERF.md, PR 37, call 19): the plain lowering's many
    # small operations cost at any length
    return not interpret_mode()


register_impl("gated_delta_rule", platform="pallas", requires=_requires,
              predicate=_applicable, scope="delta_rule")(gated_delta_rule)
