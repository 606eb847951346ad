"""Word2Vec — skip-gram / CBOW with negative sampling.

Reference analog: org.deeplearning4j.models.word2vec.Word2Vec (+ Builder) on
top of SequenceVectors/AbstractCache; the reference trains with per-thread
Hogwild updates over individual pairs. TPU-first redesign: pair generation is
host-side numpy; the update is one jitted XLA step over a BATCH of
(center, context, negatives[k]) triples — embedding scatter-adds come from
the gradient of gather, which XLA fuses; the MXU sees one [batch, dim] x
[dim, k+1] matmul per step instead of scalar dot products.
"""

from __future__ import annotations

import functools
import os
from typing import Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nlp.tokenizers import CommonPreprocessor, DefaultTokenizerFactory
from deeplearning4j_tpu.nlp.vocab import (NegativeSampler, VocabCache,
                                          build_alias_table,
                                          cosine_similarity)


def cbow_windows(encoded, window: int):
    """(center [N], context-window [N, 2*window]) arrays over encoded
    sentences; short windows are padded by cycling the available context
    words. Shared by Word2Vec (CBOW) and ParagraphVectors (PV-DM)."""
    centers, ctxs = [], []
    for sent in encoded:
        n = len(sent)
        for i in range(n):
            ctx = [int(sent[j]) for j in range(max(0, i - window),
                                               min(n, i + window + 1)) if j != i]
            if not ctx:
                continue
            centers.append(int(sent[i]))
            ctxs.append([ctx[k % len(ctx)] for k in range(2 * window)])
    return (np.asarray(centers, np.int32),
            np.asarray(ctxs, np.int32).reshape(-1, 2 * window))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _sg_neg_step(W, C, center, context, negatives, lr):
    """One negative-sampling SGD step.

    W [V, D] input vectors, C [V, D] output vectors; center [B], context [B],
    negatives [B, K]. Loss = -log σ(w·c) - Σ log σ(-w·n).
    """

    def loss_fn(params):
        W_, C_ = params
        w = W_[center]                       # [B, D]
        pos = jnp.einsum("bd,bd->b", w, C_[context])
        neg = jnp.einsum("bd,bkd->bk", w, C_[negatives])
        return -jax.nn.log_sigmoid(pos).sum() - jax.nn.log_sigmoid(-neg).sum()

    loss, grads = jax.value_and_grad(loss_fn)((W, C))
    W = W - lr * grads[0]
    C = C - lr * grads[1]
    return W, C, loss


@functools.partial(jax.jit, donate_argnums=(0, 1),
                   static_argnames=("k",))
def _sg_neg_steps_devneg(W, C, key, centers, contexts, aprob, aalias, lr, k):
    """S sequential negative-sampling steps in ONE dispatch: centers [S, B]
    and contexts [S, B] scanned over axis 0, so one host->device transfer
    and one XLA launch cover S batches — per-batch dispatch latency
    amortizes S-fold while the update math stays bit-identical to S calls
    of _sg_neg_step.

    Negatives are sampled ON DEVICE from a Vose alias table (aprob [V]
    f32, aalias [V] i32) — the host ships only (center, context) pairs
    (uint16 when the vocab fits), cutting host->device bytes 14x vs
    staging int32 (center, context, negs[S, B, K]). Distribution is the
    same unigram^0.75 (alias method); draws come from the JAX PRNG
    instead of the host stream."""
    V = W.shape[0]

    def body(carry, batch):
        W_, C_, key_ = carry
        center, context = (b.astype(jnp.int32) for b in batch)
        key_, k1, k2 = jax.random.split(key_, 3)
        idx = jax.random.randint(k1, (center.shape[0], k), 0, V)
        u = jax.random.uniform(k2, (center.shape[0], k))
        negs = jnp.where(u < aprob[idx], idx, aalias[idx])

        def loss_fn(params):
            Wp, Cp = params
            w = Wp[center]
            pos = jnp.einsum("bd,bd->b", w, Cp[context])
            neg = jnp.einsum("bd,bkd->bk", w, Cp[negs])
            return (-jax.nn.log_sigmoid(pos).sum()
                    - jax.nn.log_sigmoid(-neg).sum())

        loss, g = jax.value_and_grad(loss_fn)((W_, C_))
        return (W_ - lr * g[0], C_ - lr * g[1], key_), loss

    (W, C, _), losses = jax.lax.scan(body, (W, C, key), (centers, contexts))
    return W, C, losses.sum()


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _cbow_neg_step(W, C, context_win, center, negatives, lr):
    """CBOW: mean of context window vectors predicts the center word.
    context_win [B, 2w] (padded with center index where window clipped)."""

    def loss_fn(params):
        W_, C_ = params
        h = W_[context_win].mean(axis=1)     # [B, D]
        pos = jnp.einsum("bd,bd->b", h, C_[center])
        neg = jnp.einsum("bd,bkd->bk", h, C_[negatives])
        return -jax.nn.log_sigmoid(pos).sum() - jax.nn.log_sigmoid(-neg).sum()

    loss, grads = jax.value_and_grad(loss_fn)((W, C))
    return W - lr * grads[0], C - lr * grads[1], loss


def build_huffman(freqs) -> tuple:
    """Huffman coding over word frequencies (the reference's Huffman class in
    deeplearning4j-nlp, used by its default hierarchical softmax).

    Returns (codes [V, L] int8 0/1, points [V, L] int32 inner-node ids,
    mask [V, L] float32) padded to the longest code length L — fixed shapes
    so the HS step jits once.
    """
    import heapq

    V = len(freqs)
    if V == 1:
        return (np.zeros((1, 1), np.int8), np.zeros((1, 1), np.int32),
                np.ones((1, 1), np.float32))
    heap = [(int(f), i, None, None) for i, f in enumerate(freqs)]
    heapq.heapify(heap)
    next_id = V
    nodes = {}
    while len(heap) > 1:
        f1, id1, l1, r1 = heapq.heappop(heap)
        f2, id2, l2, r2 = heapq.heappop(heap)
        nodes[next_id] = (id1, id2)
        heapq.heappush(heap, (f1 + f2, next_id, id1, id2))
        next_id += 1
    root = heap[0][1]

    codes: list = [None] * V
    points: list = [None] * V

    def walk(node, code, path):
        if node < V:
            codes[node] = code
            points[node] = path
            return
        left, right = nodes[node]
        # inner-node parameter index: node - V (V-1 inner nodes total)
        walk(left, code + [0], path + [node - V])
        walk(right, code + [1], path + [node - V])

    walk(root, [], [])
    L = max(len(c) for c in codes)
    code_m = np.zeros((V, L), np.int8)
    point_m = np.zeros((V, L), np.int32)
    mask_m = np.zeros((V, L), np.float32)
    for i in range(V):
        n = len(codes[i])
        code_m[i, :n] = codes[i]
        point_m[i, :n] = points[i]
        mask_m[i, :n] = 1.0
    return code_m, point_m, mask_m


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _sg_hs_step(W, Theta, accW, accT, center, context, codes, points, mask, lr):
    """Hierarchical-softmax skip-gram step with Adagrad scaling.

    For a (center, context) pair the loss walks the CONTEXT word's Huffman
    path with the center's input vector:
    loss = -sum_l mask * log sigma((1-2*code_l) * w . theta_l);
    Theta holds one vector per inner node ([V-1, D]).

    The summed batch loss concentrates B gradient contributions on the few
    inner nodes near the Huffman root (plain SGD diverges there at any lr
    that still moves the leaves), so the update is Adagrad-normalized per
    parameter — the classic fix for embedding-frequency imbalance; accW/accT
    carry the squared-gradient accumulators across batches."""

    def loss_fn(params):
        W_, T_ = params
        w = W_[center]                           # [B, D]
        th = T_[points[context]]                 # [B, L, D]
        sign = 1.0 - 2.0 * codes[context].astype(jnp.float32)  # [B, L]
        logits = sign * jnp.einsum("bd,bld->bl", w, th)
        logp = jax.nn.log_sigmoid(logits) * mask[context]
        return -logp.sum()

    loss, g = jax.value_and_grad(loss_fn)((W, Theta))
    accW = accW + g[0] * g[0]
    accT = accT + g[1] * g[1]
    W = W - lr * g[0] / jnp.sqrt(accW + 1e-8)
    Theta = Theta - lr * g[1] / jnp.sqrt(accT + 1e-8)
    return W, Theta, accW, accT, loss


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _sg_hs_steps(W, Theta, accW, accT, centers, contexts, codes, points,
                 mask, lr):
    """S sequential hierarchical-softmax steps in one dispatch (the scan
    twin of _sg_hs_step; see _sg_neg_steps_devneg for why): centers/contexts
    [S, B] scanned; the Huffman tables ride along unscanned."""

    def body(carry, batch):
        W_, T_, aW, aT = carry
        center, context = batch

        def loss_fn(params):
            Wp, Tp = params
            w = Wp[center]
            th = Tp[points[context]]
            sign = 1.0 - 2.0 * codes[context].astype(jnp.float32)
            logits = sign * jnp.einsum("bd,bld->bl", w, th)
            return -(jax.nn.log_sigmoid(logits) * mask[context]).sum()

        loss, g = jax.value_and_grad(loss_fn)((W_, T_))
        aW = aW + g[0] * g[0]
        aT = aT + g[1] * g[1]
        return (W_ - lr * g[0] / jnp.sqrt(aW + 1e-8),
                T_ - lr * g[1] / jnp.sqrt(aT + 1e-8), aW, aT), loss

    (W, Theta, accW, accT), losses = jax.lax.scan(
        body, (W, Theta, accW, accT), (centers, contexts))
    return W, Theta, accW, accT, losses.sum()


class Word2Vec:
    """Builder-style Word2Vec (reference: Word2Vec.Builder()...build().fit()).

    ``hs=True`` selects hierarchical softmax over a Huffman tree (the
    reference's default); otherwise negative sampling with ``negative``
    noise words."""

    def __init__(self, vector_size: int = 100, window: int = 5,
                 min_count: int = 1, negative: int = 5, epochs: int = 1,
                 learning_rate: float = 0.025, cbow: bool = False,
                 subsample: float = 0.0, batch_size: int = 512, seed: int = 42,
                 hs: bool = False, workers: int = 0,
                 min_learning_rate: Optional[float] = None):
        self.vector_size = vector_size
        # linear lr decay over the run's words, floored here (reference:
        # Word2Vec.Builder().minLearningRate — its alpha decays with words
        # processed). None keeps the fixed-lr behavior.
        self.min_lr = min_learning_rate
        self.window = window
        self.negative = negative
        self.hs = hs
        # host-side worker threads for the native concurrent front
        # (reference: Word2Vec.Builder().workers(n) — its Hogwild thread
        # count); 0 = auto
        self.workers = workers if workers > 0 else min(8, os.cpu_count() or 4)
        self.epochs = epochs
        self.lr = learning_rate
        self.cbow = cbow
        self.subsample = subsample
        self.batch_size = batch_size
        self.seed = seed
        self.vocab = VocabCache(min_count=min_count)
        self.tokenizer = DefaultTokenizerFactory(CommonPreprocessor())
        self.W: Optional[np.ndarray] = None   # input vectors (the embeddings)
        self.C: Optional[np.ndarray] = None   # output vectors

    # ------------------------------------------------------------------- fit
    def _iter_token_sents(self, corpus):
        """Streaming tokenized-sentence view of ``corpus``: a string (split
        on lines), any iterable of strings/token-lists, or a
        nlp.corpus.SentenceIterator — nothing is materialized, so file-
        backed corpora train at any size (r4). For epochs > 1 the corpus
        must be re-iterable (iterators expose reset(); plain generators
        are single-pass)."""
        if isinstance(corpus, str):
            corpus = corpus.splitlines()
        for line in corpus:
            toks = (self.tokenizer.tokenize(line) if isinstance(line, str)
                    else list(line))
            if toks:
                yield toks

    def _pairs(self, encoded: List[np.ndarray], rng) -> np.ndarray:
        """All (center, context) skip-gram pairs with random window shrink.

        Vectorized over the whole chunk (r5): sentences concatenate into
        one flat token array with per-token sentence positions, and each
        offset d in 1..window contributes its valid left/right pairs in
        two boolean-mask passes — no per-token Python loop. The measured
        host windowing rate went from ~50k words/sec (the r4 double loop,
        a 40x bottleneck under the 2M words/sec device step) to the
        numpy-bound rate; pair semantics are identical (one uniform
        window shrink b per center, both directions share it)."""
        lens = np.asarray([len(s) for s in encoded], np.int64)
        total = int(lens.sum())
        if total == 0:
            return np.zeros((0, 2), np.int32)
        flat = np.concatenate([np.asarray(s, np.int32) for s in encoded])
        starts = np.repeat(np.cumsum(lens) - lens, lens)
        pos = np.arange(total) - starts          # position within sentence
        slen = np.repeat(lens, lens)
        b = rng.integers(1, self.window + 1, total)
        cs, xs = [], []
        for d in range(1, self.window + 1):
            reach = b >= d
            right = reach & (pos + d < slen)
            left = reach & (pos >= d)
            ri = np.nonzero(right)[0]
            li = np.nonzero(left)[0]
            cs.append(flat[ri])
            xs.append(flat[ri + d])
            cs.append(flat[li])
            xs.append(flat[li - d])
        return np.stack([np.concatenate(cs), np.concatenate(xs)],
                        axis=1).astype(np.int32)

    # ------------------------------------------------- native concurrent front
    def _native_corpus_path(self, corpus) -> Optional[str]:
        """File path when ``corpus`` qualifies for the native concurrent
        front (see _fit_native), else None."""
        from deeplearning4j_tpu.native.lib import native_available
        from deeplearning4j_tpu.nlp.corpus import LineSentenceIterator

        if (type(corpus) is LineSentenceIterator
                and corpus.preprocessor is None
                and corpus.encoding.lower().replace("-", "") == "utf8"
                and not self.cbow
                and type(self.tokenizer) is DefaultTokenizerFactory
                and type(self.tokenizer.preprocessor) is CommonPreprocessor
                and os.path.isfile(corpus.path)
                and native_available()):
            return corpus.path
        return None

    @staticmethod
    def _ascii_sample(path: str, limit: int = 1 << 20) -> bool:
        """True when ``limit`` bytes sampled at the file's head, middle,
        and tail are pure ASCII (ADVICE r5: head-only sampling let late
        non-ASCII content ride the native front and silently diverge the
        vocabulary). The native tokenizer only matches the Python one
        (lowercase + [^\\w\\s] strip) for ASCII text — non-ASCII bytes pass
        through unlowercased and unicode punctuation survives — so AUTO
        selection requires ASCII samples; ``native_front=True`` overrides
        (byte-level semantics, documented in nlp.native_text)."""
        size = os.path.getsize(path)
        if size <= limit:
            offsets, chunk = [0], limit
        else:
            chunk = limit // 3
            offsets = [0, max(0, size // 2 - chunk // 2), size - chunk]
        with open(path, "rb") as f:
            for off in offsets:
                f.seek(off)
                sample = f.read(chunk)
                if sample and max(sample) >= 0x80:
                    return False
        return True

    def _lr_at(self, words_done: int, total_words: int) -> float:
        """Linear lr decay over the run's in-vocab words (the reference's
        alpha schedule), floored at min_learning_rate; fixed lr when the
        floor is unset. lr rides the jitted steps as a traced operand, so
        the per-chunk value never recompiles."""
        if self.min_lr is None:
            return self.lr
        frac = min(1.0, words_done / max(1, total_words))
        return max(self.min_lr, self.lr * (1.0 - frac))

    def _fit_native(self, path: str, rng) -> Optional["Word2Vec"]:
        """Train over the native concurrent text front: N C++ threads
        tokenize/encode/subsample/window/negative-sample line-chunks in
        parallel (native/dl4jtpu_native.cpp) while this thread runs the
        jitted device step — the reference's Hogwild host concurrency with
        a single-program device side. Like the reference's threaded
        trainer, batch arrival order is nondeterministic run-to-run; pass
        ``native_front=False`` to fit() for the deterministic Python
        stream. None = native pass unavailable (caller falls back)."""
        from deeplearning4j_tpu.nlp.native_text import (NativeSkipGramStream,
                                                        native_word_counts)

        counts = native_word_counts(path, self.workers)
        if counts is None:
            return None
        self.vocab.fit_from_counts(counts)
        V, D = len(self.vocab), self.vector_size
        if V == 0:
            raise ValueError("empty vocabulary")
        self.W = ((rng.random((V, D), np.float32) - 0.5) / D)
        self.C = np.zeros((V, D), np.float32)
        keep = (self.vocab.subsample_keep_probs(self.subsample)
                if self.subsample > 0 else None)
        W, C = jnp.asarray(self.W), jnp.asarray(self.C)
        if self.hs:
            freqs = [self.vocab.counts[w_] for w_ in self.vocab.words]
            codes_m, points_m, mask_m = (jnp.asarray(a)
                                         for a in build_huffman(freqs))
            C = jnp.zeros((max(V - 1, 1), D), jnp.float32)
            accW, accT = jnp.zeros_like(W), jnp.zeros_like(C)
            probs, negative = None, 0
        else:
            probs = self.vocab.unigram_table_probs()
            aprob, aalias = build_alias_table(probs)
            aprob, aalias = jnp.asarray(aprob), jnp.asarray(aalias)
            key = jax.random.PRNGKey(self.seed)
            tail_sampler = NegativeSampler(probs)
        # the C++ side ships ONLY (center, context) pairs — negatives are
        # sampled on-device from the alias table inside the scanned step,
        # and pair ids ride as uint16 when the vocab fits: 14x fewer
        # host->device bytes than staging int32 (center, context, negs[K])
        total_words = self.vocab._total * self.epochs
        stream = NativeSkipGramStream(
            path, self.vocab.words, None, keep, self.window, 0,
            self.batch_size, seed=self.seed, n_threads=self.workers)
        # S batches ride each dispatch via the scanned step — per-batch
        # launch latency amortizes S-fold; the tail shorter than S runs on
        # the per-batch step with host-sampled negatives. S=32 measured
        # best on-chip (S=16: 528k, S=32: 619k, S=64+: tail-dominated)
        S, B = 32, self.batch_size
        pair_dt = np.uint16 if V <= 0xFFFF else np.int32
        cs = np.empty((S, B), pair_dt)
        xs = np.empty((S, B), pair_dt)
        try:
            for epoch in range(self.epochs):
                if epoch:
                    stream.reset()
                k = 0
                for c, x, _ in stream:
                    cs[k], xs[k] = c, x
                    k += 1
                    if k == S:
                        # PRODUCER-side schedule, like the reference: the
                        # original word2vec decays alpha by words READ per
                        # thread, and our C++ workers publish exactly that
                        # counter. It runs ahead of applied updates by the
                        # worker-buffer/queue lead (bounded; negligible on
                        # real corpora, up to an epoch on tiny ones)
                        lr_now = self._lr_at(stream.words_seen, total_words)
                        if self.hs:
                            W, C, accW, accT, _ = _sg_hs_steps(
                                W, C, accW, accT, jnp.asarray(cs),
                                jnp.asarray(xs), codes_m, points_m, mask_m,
                                lr=lr_now)
                        else:
                            key, sub = jax.random.split(key)
                            W, C, _ = _sg_neg_steps_devneg(
                                W, C, sub, jnp.asarray(cs), jnp.asarray(xs),
                                aprob, aalias, lr=lr_now, k=self.negative)
                        k = 0
                rng_tail = np.random.default_rng(self.seed + 31 * epoch)
                lr_now = self._lr_at(stream.words_seen, total_words)
                for i in range(k):
                    ci = cs[i].astype(np.int32)
                    xi = xs[i].astype(np.int32)
                    if self.hs:
                        W, C, accW, accT, _ = _sg_hs_step(
                            W, C, accW, accT, jnp.asarray(ci),
                            jnp.asarray(xi), codes_m, points_m, mask_m,
                            lr=lr_now)
                    else:
                        negs = tail_sampler.sample(rng_tail,
                                                   (B, self.negative))
                        W, C, _ = _sg_neg_step(W, C, jnp.asarray(ci),
                                               jnp.asarray(xi),
                                               jnp.asarray(negs),
                                               lr=lr_now)
        finally:
            stream.close()
        self.W, self.C = np.asarray(W), np.asarray(C)
        return self

    def fit(self, corpus, chunk_sentences: int = 4096,
            native_front: Optional[bool] = None) -> "Word2Vec":
        """Fit on a sentence corpus.

        **Determinism note:** even with a fixed ``seed``, eligible runs
        (file-backed ASCII LineSentenceIterator corpus, skip-gram config,
        default tokenizer, loadable native lib) AUTO-ROUTE to the native
        concurrent front, whose multi-threaded batch arrival order is
        NONDETERMINISTIC run-to-run — exactly like the reference's Hogwild
        workers, the same seed no longer reproduces embeddings
        bit-for-bit. Pass ``native_front=False`` to force the
        deterministic (seed-reproducible) Python stream, or ``True`` to
        require the concurrent native path.

        Two streaming passes per epoch over ``corpus`` (r4): pass 1
        builds the vocabulary sentence-by-sentence; each epoch then streams
        sentences again, encoding + subsampling on the fly and training in
        chunks of ``chunk_sentences`` — the corpus itself is never
        materialized, so file-backed SentenceIterators (nlp.corpus) train
        at any size. Batch shapes are fixed, so every chunk reuses the one
        compiled XLA step.

        ``native_front``: None (default) auto-selects the native concurrent
        host pipeline when the corpus is a plain file-backed
        LineSentenceIterator, the config is skip-gram (neg-sampling or HS)
        with the default tokenizer, and the native lib loads; True requires
        it (raising otherwise); False forces the deterministic Python
        stream."""
        rng = np.random.default_rng(self.seed)
        if self.hs and self.cbow:
            raise ValueError("cbow=True with hs=True is not supported; use "
                             "negative sampling for CBOW")
        path = (None if native_front is False
                else self._native_corpus_path(corpus))
        if native_front is True and path is None:
            raise ValueError(
                "native_front=True requires a file-backed "
                "LineSentenceIterator (no preprocessor, utf-8), a skip-gram "
                "config with the default tokenizer, and a loadable native "
                "library")
        if (native_front is None and path is not None
                and not self._ascii_sample(path)):
            # auto mode only routes ASCII corpora natively: tokenization
            # of non-ASCII text diverges from the Python front (see
            # _ascii_sample); native_front=True forces it
            path = None
        if path is not None:
            out = self._fit_native(path, rng)
            if out is not None:
                return out
        self.vocab.fit(self._iter_token_sents(corpus))
        V, D = len(self.vocab), self.vector_size
        if V == 0:
            raise ValueError("empty vocabulary")
        self.W = ((rng.random((V, D), np.float32) - 0.5) / D)
        self.C = np.zeros((V, D), np.float32)
        sampler = NegativeSampler(self.vocab.unigram_table_probs())
        keep = (self.vocab.subsample_keep_probs(self.subsample)
                if self.subsample > 0 else None)

        W, C = jnp.asarray(self.W), jnp.asarray(self.C)
        huffman = None
        accW = accT = None
        if self.hs and not self.cbow:
            # per-fit: the tree depends on THIS corpus's vocabulary
            freqs = [self.vocab.counts[w_] for w_ in self.vocab.words]
            huffman = tuple(jnp.asarray(a) for a in build_huffman(freqs))
            C = jnp.asarray(np.zeros((max(V - 1, 1), D), np.float32))
            accW = jnp.zeros_like(W)
            accT = jnp.zeros_like(C)

        def train_chunk(encoded, lr):
            nonlocal W, C, accW, accT
            if self.cbow:
                centers, ctxs = cbow_windows(encoded, self.window)
                if len(centers) == 0:
                    return
                order = rng.permutation(len(centers))
                centers, ctxs = centers[order], ctxs[order]
                B = min(self.batch_size, len(centers))
                for s in range(0, (len(centers) // B) * B, B):
                    negs = sampler.sample(rng, (B, self.negative))
                    W, C, _ = _cbow_neg_step(W, C, jnp.asarray(ctxs[s:s + B]),
                                             jnp.asarray(centers[s:s + B]),
                                             jnp.asarray(negs), lr=lr)
            elif self.hs:
                pairs = self._pairs(encoded, rng)
                if len(pairs) == 0:
                    return
                codes_m, points_m, mask_m = huffman
                pairs = pairs[rng.permutation(len(pairs))]
                B = min(self.batch_size, len(pairs))
                for s in range(0, (len(pairs) // B) * B, B):
                    batch = pairs[s:s + B]
                    W, C, accW, accT, _ = _sg_hs_step(
                        W, C, accW, accT, jnp.asarray(batch[:, 0]),
                        jnp.asarray(batch[:, 1]),
                        codes_m, points_m, mask_m, lr=lr)
            else:
                pairs = self._pairs(encoded, rng)
                if len(pairs) == 0:
                    return
                pairs = pairs[rng.permutation(len(pairs))]
                # batches reuse one compiled step shape; negatives for the
                # WHOLE chunk come from one sampler call (r5 — per-batch
                # searchsorted calls were a measured host hot spot)
                B = min(self.batch_size, len(pairs))
                nb = len(pairs) // B
                negs_all = sampler.sample(rng, (nb, B, self.negative))
                for k in range(nb):
                    s = k * B
                    batch = pairs[s:s + B]
                    W, C, _ = _sg_neg_step(W, C, jnp.asarray(batch[:, 0]),
                                           jnp.asarray(batch[:, 1]),
                                           jnp.asarray(negs_all[k]),
                                           lr=lr)

        total_words = self.vocab._total * self.epochs
        words_done = 0
        for epoch in range(self.epochs):
            if hasattr(corpus, "reset"):
                corpus.reset()
            buf = []
            seen = 0
            for toks in self._iter_token_sents(corpus):
                seen += 1
                enc = self.vocab.encode(toks)
                words_done += len(enc)
                if keep is not None and len(enc):
                    enc = enc[rng.random(len(enc)) < keep[enc]]
                if len(enc):
                    buf.append(enc)
                if len(buf) >= chunk_sentences:
                    train_chunk(buf, self._lr_at(words_done, total_words))
                    buf = []
            if buf:
                train_chunk(buf, self._lr_at(words_done, total_words))
            if seen == 0 and epoch == 0:
                # a single-pass generator was exhausted by the vocabulary
                # pass — fail loud instead of returning random embeddings
                raise ValueError(
                    "corpus yielded no sentences on the training pass; "
                    "fit() makes one vocabulary pass plus one pass per "
                    "epoch, so pass a re-iterable (list, str, or a "
                    "nlp.corpus SentenceIterator), not a generator")
        self.W, self.C = np.asarray(W), np.asarray(C)
        return self

    # ----------------------------------------------------------------- query
    def get_word_vector(self, word: str) -> Optional[np.ndarray]:
        i = self.vocab.index_of(word)
        return None if i < 0 else self.W[i]

    def similarity(self, a: str, b: str) -> float:
        return cosine_similarity(self.get_word_vector(a), self.get_word_vector(b))

    def words_nearest(self, word=None, top: int = 10, positive=None,
                      negative=None) -> List[str]:
        """wordsNearest — cosine neighbors of a word, or of an analogy
        query (reference: wordsNearest(positive, negative, top), the
        king - man + woman form)."""
        from deeplearning4j_tpu.nlp.vocab import nearest_neighbors

        return nearest_neighbors(self.vocab.words, self.vocab.index, self.W,
                                 word=word, top=top, positive=positive,
                                 negative=negative)

    # ----------------------------------------------------------------- serde
    def save(self, path: str):
        np.savez(path, W=self.W, C=self.C,
                 words=np.asarray(self.vocab.words, dtype=object))

    @classmethod
    def load(cls, path: str) -> "Word2Vec":
        data = np.load(path if path.endswith(".npz") else path + ".npz",
                       allow_pickle=True)
        m = cls(vector_size=data["W"].shape[1])
        m.W, m.C = data["W"], data["C"]
        words = [str(w) for w in data["words"]]
        m.vocab.words = words
        m.vocab.index = {w: i for i, w in enumerate(words)}
        return m
