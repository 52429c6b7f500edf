"""Word2Vec: skip-gram / CBOW with negative sampling, batched for TPU.

Ref: `models/word2vec/Word2Vec.java:71` (extends SequenceVectors; fit at
`models/sequencevectors/SequenceVectors.java:244`), learning algorithms
`models/embeddings/learning/impl/elements/{SkipGram,CBOW}.java`, unigram
negative-sampling table `models/embeddings/loader/` and subsampling as in
the original word2vec.c the reference mirrors.

TPU-first: the reference updates one pair at a time (axpy per row). Here
an epoch's (center, context) pairs are generated on host as index arrays
and consumed in fixed-size batches by ONE jitted step — embedding
gathers, a [B, 1+neg] batched dot, and scatter-add updates — so the work
is dense MXU/VPU math instead of pointer chasing. Negative samples are
drawn inside the step from the unigram^0.75 table via jax.random.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .tokenization import CommonPreprocessor, DefaultTokenizerFactory
from .vocab import HuffmanTree, VocabCache


def _as_sentences(data, tokenizer) -> List[List[str]]:
    out = []
    for item in data:
        if isinstance(item, str):
            out.append(tokenizer.tokenize(item))
        else:
            out.append(list(item))
    return out


class _EmbeddingModel:
    """Shared lookup-table API (ref: WordVectors interface —
    getWordVector, wordsNearest, similarity)."""

    vocab: VocabCache
    syn0: np.ndarray  # [V, D] input vectors

    def word_vector(self, word: str) -> Optional[np.ndarray]:
        i = self.vocab.index_of(word)
        return None if i < 0 else np.asarray(self.syn0[i])

    def has_word(self, word: str) -> bool:
        return self.vocab.contains_word(word)

    def similarity(self, w1: str, w2: str) -> float:
        a, b = self.word_vector(w1), self.word_vector(w2)
        if a is None or b is None:
            return float("nan")
        denom = (np.linalg.norm(a) * np.linalg.norm(b)) + 1e-12
        return float(a @ b / denom)

    def words_nearest(self, word_or_vec: Union[str, np.ndarray],
                      top_n: int = 10) -> List[str]:
        if isinstance(word_or_vec, str):
            vec = self.word_vector(word_or_vec)
            exclude = {word_or_vec}
        else:
            vec = np.asarray(word_or_vec)
            exclude = set()
        if vec is None:
            return []
        m = np.asarray(self.syn0)
        sims = (m @ vec) / ((np.linalg.norm(m, axis=1) + 1e-12)
                            * (np.linalg.norm(vec) + 1e-12))
        order = np.argsort(-sims)
        out = []
        for i in order:
            w = self.vocab.word_at_index(int(i))
            if w not in exclude:
                out.append(w)
            if len(out) >= top_n:
                break
        return out


# examples per device upload: each scanned chunk materializes at most
# this many rows on device, so epoch memory stays bounded for
# arbitrarily large corpora (the scan eliminates per-batch dispatch; the
# chunking keeps its memory profile streaming-like)
_MEGABATCH = 1 << 20


def _batch_geometry(n: int, batch_size: int, stable_shapes: bool):
    """(B, nb) the padder will use for an n-example chunk — shared by
    `_pad_to_batches` (materialization) and `_padded_total` (lr-schedule
    accounting), so the two can never disagree."""
    p2 = 1 << (n - 1).bit_length()
    B = min(int(batch_size), p2)
    nb = -(-n // B)
    if stable_shapes and nb > 32:  # bucket to the next multiple of ~nb/32
        q = 1 << max(0, nb.bit_length() - 6)
        nb = -(-nb // q) * q
    return B, nb


def _padded_total(n: int, batch_size: int, stable_shapes: bool) -> int:
    """Total example SLOTS (incl. wrap padding) the chunked padder emits
    for n examples — what the linear lr schedule must count, or the decay
    finishes early by the duplication factor."""
    tot = 0
    for off in range(0, max(n, 1), _MEGABATCH):
        m = min(_MEGABATCH, n - off)
        if m <= 0:
            break
        B, nb = _batch_geometry(m, batch_size, stable_shapes)
        tot += nb * B
    return tot


def _iter_example_chunks(cols, batch_size: int, stable_shapes: bool):
    """Yield `_pad_to_batches` results over fixed-size slices of the
    (already shuffled) example columns. Full chunks share one compiled
    shape; only the tail chunk differs (bucketed when stable_shapes)."""
    n = len(cols[0])
    for off in range(0, max(n, 1), _MEGABATCH):
        chunk = tuple(c[off:off + _MEGABATCH] for c in cols)
        batches, B, tot = _pad_to_batches(chunk, batch_size, stable_shapes)
        if batches is not None:
            yield batches, B, tot


def _pad_to_batches(cols, batch_size: int, stable_shapes: bool = True):
    """Shared batching for the scanned-epoch trainers (word2vec / glove /
    paragraph vectors): wrap-pad the shuffled example columns and reshape
    to [nb, B, ...] for `lax.scan`.

    Shapes are bucketed so the jitted epoch function compiles for few
    distinct shapes instead of once per epoch (the dynamic-window pair
    count varies every epoch): B is the configured batch size (or
    next_pow2(n) for corpora smaller than one batch), and the batch
    count is rounded up to a ~1/32-granularity bucket — ~3% wrapped
    duplicate work for corpora of at least one batch (up to ~2x only for
    sub-batch corpora, where B pads to next_pow2(n); callers account for
    the padding in lr schedules via `_padded_total`). Every epoch
    reshuffles, so the training multiset stays unbiased. Returns
    (batches, B, total_slots), or (None, 0, 0) for a zero-example epoch
    (callers skip it)."""
    n = len(cols[0])
    if n == 0:
        return None, 0, 0
    B, nb = _batch_geometry(n, batch_size, stable_shapes)
    tot = nb * B
    reps = -(-tot // n)  # tot >= n, so reps > 1 exactly when padding needed
    if reps > 1:
        cols = tuple(np.concatenate([c] * reps, 0)[:tot] for c in cols)
    batches = tuple(jnp.asarray(c.reshape((nb, B) + c.shape[1:]))
                    for c in cols)
    return batches, B, tot


def _neg_table(vocab: VocabCache, size: int = 1 << 17,
               power: float = 0.75) -> np.ndarray:
    counts = vocab.counts_array() ** power
    probs = counts / counts.sum()
    # expanded multinomial table (word2vec.c style, sized for gather)
    reps = np.maximum(1, np.round(probs * size)).astype(np.int64)
    return np.repeat(np.arange(len(probs)), reps).astype(np.int32)


def _gen_pairs(sentences_idx: List[np.ndarray], window: int,
               rng: np.random.RandomState):
    """Dynamic-window (center, context) pairs (ref: SkipGram.java uses
    b ~ U(0, window) shrinkage like word2vec.c).

    Vectorized: for each offset d in [1, window], one boolean mask picks
    the centers whose shrunk window covers d — O(window) numpy ops per
    sentence instead of a per-token python loop (same pair multiset as
    the naive nested loop; ordering differs but every epoch shuffles)."""
    centers, contexts = [], []
    for s in sentences_idx:
        n = len(s)
        if n < 2:
            continue
        b = rng.randint(1, window + 1, size=n)
        for d in range(1, window + 1):
            if d >= n:
                break
            sel = b >= d
            right = sel[:n - d]       # context at i + d
            if right.any():
                centers.append(s[:n - d][right])
                contexts.append(s[d:][right])
            left = sel[d:]            # context at i - d
            if left.any():
                centers.append(s[d:][left])
                contexts.append(s[:n - d][left])
    if not centers:
        return (np.zeros(0, np.int32),) * 2
    return (np.concatenate(centers).astype(np.int32),
            np.concatenate(contexts).astype(np.int32))


def _gen_cbow(sentences_idx: List[np.ndarray], window: int,
              rng: np.random.RandomState):
    """CBOW windows: (center, padded context matrix, mask) — the whole
    window averages into one prediction (ref: CBOW.java).

    Vectorized like _gen_pairs: column 2(d-1) holds the i-d context,
    column 2(d-1)+1 the i+d context, masked where the shrunk window or
    the sentence boundary excludes them (the mean over masked entries is
    layout-independent, so the packed-vs-fixed column order does not
    change the model)."""
    W = 2 * window
    centers, ctx, mask = [], [], []
    for s in sentences_idx:
        n = len(s)
        if n < 2:
            continue
        b = rng.randint(1, window + 1, size=n)
        row = np.zeros((n, W), np.int64)
        m = np.zeros((n, W), np.float32)
        idx = np.arange(n)
        for d in range(1, window + 1):
            covered = b >= d
            left = covered & (idx >= d)
            right = covered & (idx < n - d)
            row[left, 2 * (d - 1)] = s[idx[left] - d]
            m[left, 2 * (d - 1)] = 1.0
            row[right, 2 * (d - 1) + 1] = s[idx[right] + d]
            m[right, 2 * (d - 1) + 1] = 1.0
        keep = m.any(axis=1)
        if keep.any():
            centers.append(s[keep])
            ctx.append(row[keep])
            mask.append(m[keep])
    if not centers:
        return (np.zeros(0, np.int32), np.zeros((0, W), np.int32),
                np.zeros((0, W), np.float32))
    return (np.concatenate(centers).astype(np.int32),
            np.concatenate(ctx).astype(np.int32),
            np.concatenate(mask).astype(np.float32))


class Word2Vec(_EmbeddingModel):
    """Ref: Word2Vec.java:71 + Builder. Both elements learning algorithms
    (skip-gram, CBOW) with negative sampling."""

    def __init__(self, layer_size: int = 100, window_size: int = 5,
                 min_word_frequency: int = 1, learning_rate: float = 0.025,
                 min_learning_rate: float = 1e-4, negative: int = 5,
                 subsampling: float = 0.0, epochs: int = 1,
                 iterations: int = 1, batch_size: int = 1024,
                 elements_learning_algorithm: str = "skipgram",
                 seed: int = 42, tokenizer_factory=None,
                 use_hierarchic_softmax: bool = False):
        self.layer_size = layer_size
        self.window_size = window_size
        self.min_word_frequency = min_word_frequency
        self.learning_rate = learning_rate
        self.min_learning_rate = min_learning_rate
        self.negative = negative
        self.subsampling = subsampling
        self.epochs = epochs
        self.iterations = iterations
        self.batch_size = batch_size
        self.algorithm = elements_learning_algorithm.lower()
        if self.algorithm not in ("skipgram", "cbow"):
            raise ValueError(f"unknown algorithm {self.algorithm!r}")
        self.seed = seed
        self.tokenizer = tokenizer_factory or DefaultTokenizerFactory(
            CommonPreprocessor())
        self.use_hs = use_hierarchic_softmax
        self.vocab = VocabCache(min_word_frequency)
        self.syn0: Optional[np.ndarray] = None
        self.syn1: Optional[np.ndarray] = None

    # -- builder parity ------------------------------------------------
    class Builder:
        _FIELDS = {"layer_size", "window_size", "min_word_frequency",
                   "learning_rate", "min_learning_rate", "negative",
                   "subsampling", "epochs", "iterations", "batch_size",
                   "elements_learning_algorithm", "seed",
                   "tokenizer_factory", "use_hierarchic_softmax"}

        def __init__(self):
            self._kw = {}

        def __getattr__(self, name):
            if name in Word2Vec.Builder._FIELDS:
                def setter(v):
                    self._kw[name] = v
                    return self
                return setter
            raise AttributeError(name)

        def build(self) -> "Word2Vec":
            return Word2Vec(**self._kw)

    @staticmethod
    def builder() -> "Word2Vec.Builder":
        return Word2Vec.Builder()

    # -- training ------------------------------------------------------
    def _subsample(self, sent_idx, counts, total, rng):
        if self.subsampling <= 0:
            return sent_idx
        t = self.subsampling
        freq = counts / total
        keep_p = np.minimum(1.0, np.sqrt(t / np.maximum(freq, 1e-12))
                            + t / np.maximum(freq, 1e-12))
        out = []
        for s in sent_idx:
            mask = rng.rand(len(s)) < keep_p[s]
            s2 = s[mask]
            if len(s2) > 1:
                out.append(s2)
        return out

    def _make_batch_step(self):
        """Single-batch update core (un-jitted) — the body of the scanned
        epoch runner. `batch` is the tuple of per-batch index arrays; `hs`
        holds the device-resident Huffman path tables (pts, codes, mask)
        for hierarchical softmax, gathered per batch ON DEVICE (the old
        path pre-gathered [N_pairs, L] paths on host — O(N·L) extra HBM
        traffic and host memory)."""
        neg = self.negative
        D = self.layer_size

        def _neg_step(syn0, syn1, v, in_rows, tgt0, table, lr, key,
                      in_weights=None):
            """Shared negative-sampling update: hidden vector v [B, D]
            predicts tgt0 [B] against `neg` sampled negatives."""
            B = v.shape[0]
            negs = table[jax.random.randint(key, (B, neg), 0,
                                            table.shape[0])]
            tgt = jnp.concatenate([tgt0[:, None], negs], 1)   # [B, 1+neg]
            u = syn1[tgt]                                      # [B,1+neg,D]
            score = jnp.einsum("bd,bkd->bk", v, u)
            label = jnp.zeros_like(score).at[:, 0].set(1.0)
            sig = jax.nn.sigmoid(score)
            g = sig - label
            loss = -(jnp.log(jnp.clip(jnp.where(label > 0, sig, 1 - sig),
                                      1e-7, 1.0))).sum(1).mean()
            gv = jnp.einsum("bk,bkd->bd", g, u)               # d loss/d v
            gu = g[:, :, None] * v[:, None, :]
            V = syn0.shape[0]
            # Per-row MEAN of the batch's pair gradients: a batch packs
            # many pairs hitting the same row (small vocabs especially);
            # summing them multiplies the effective lr per row by the
            # collision count and diverges. The reference is immune only
            # because it updates pair-at-a-time.
            if in_weights is None:
                cnt = jnp.zeros(V).at[in_rows].add(1.0)
                syn0 = syn0.at[in_rows].add(
                    -lr * gv / cnt[in_rows][:, None])
            else:
                flat = in_rows.reshape(-1)
                wflat = in_weights.reshape(-1)
                cnt = jnp.zeros(V).at[flat].add(wflat)
                upd = (gv[:, None, :] * in_weights[..., None]).reshape(-1, D)
                syn0 = syn0.at[flat].add(
                    -lr * upd / jnp.maximum(cnt[flat], 1e-8)[:, None])
            tflat = tgt.reshape(-1)
            cnt_t = jnp.zeros(V).at[tflat].add(1.0)
            syn1 = syn1.at[tflat].add(
                -lr * gu.reshape(-1, D) / cnt_t[tflat][:, None])
            return syn0, syn1, loss

        def _hs_step(syn0, syn1, v, in_rows, points, codes, cmask, lr):
            """Hierarchical-softmax update: v classifies each Huffman
            inner node on the path to the target word (ref: the Huffman
            path walk in SkipGram.java / original word2vec.c HS branch).
            points/codes/cmask: [B, L] padded paths."""
            u = syn1[points]                                   # [B, L, D]
            score = jnp.einsum("bd,bld->bl", v, u)
            sig = jax.nn.sigmoid(score)
            # label for inner node = 1 - code bit (word2vec convention)
            g = (sig - (1.0 - codes)) * cmask                  # [B, L]
            loss = -(cmask * jnp.log(jnp.clip(
                jnp.where(codes < 0.5, sig, 1 - sig), 1e-7, 1.0))
            ).sum(1).mean()
            gv = jnp.einsum("bl,bld->bd", g, u)
            gu = g[:, :, None] * v[:, None, :]
            V = syn0.shape[0]
            cnt = jnp.zeros(V).at[in_rows].add(1.0)
            syn0 = syn0.at[in_rows].add(-lr * gv / cnt[in_rows][:, None])
            pflat = points.reshape(-1)
            cnt_p = jnp.zeros(syn1.shape[0]).at[pflat].add(
                cmask.reshape(-1))
            gu_flat = gu.reshape(-1, D)
            syn1 = syn1.at[pflat].add(
                -lr * gu_flat / jnp.maximum(cnt_p[pflat], 1.0)[:, None])
            return syn0, syn1, loss

        if self.use_hs:
            if self.algorithm == "skipgram":
                def batch_step(syn0, syn1, batch, table, hs, lr, key):
                    centers, contexts = batch
                    pts, cds, cm = hs
                    v = syn0[centers]
                    # context word predicts the center's Huffman path
                    return _hs_step(syn0, syn1, v, centers, pts[contexts],
                                    cds[contexts], cm[contexts], lr)
            else:
                def batch_step(syn0, syn1, batch, table, hs, lr, key):
                    centers, ctx, mask = batch
                    pts, cds, cm = hs
                    denom = jnp.maximum(mask.sum(1, keepdims=True), 1.0)
                    v = (syn0[ctx] * mask[..., None]).sum(1) / denom
                    # input-side update distributes over the window like
                    # the neg-sampling CBOW path
                    return _hs_step(syn0, syn1, v, ctx[:, 0], pts[centers],
                                    cds[centers], cm[centers], lr)
        elif self.algorithm == "skipgram":
            def batch_step(syn0, syn1, batch, table, hs, lr, key):
                centers, contexts = batch
                v = syn0[centers]
                return _neg_step(syn0, syn1, v, centers, contexts, table,
                                 lr, key)
        else:  # cbow
            def batch_step(syn0, syn1, batch, table, hs, lr, key):
                centers, ctx, mask = batch
                denom = jnp.maximum(mask.sum(1, keepdims=True), 1.0)
                v = (syn0[ctx] * mask[..., None]).sum(1) / denom  # [B, D]
                w = mask / denom                                   # [B, W]
                return _neg_step(syn0, syn1, v, ctx, centers, table,
                                 lr, key, in_weights=w)

        return batch_step

    def _make_epoch_fn(self):
        """Whole-epoch runner: one jitted `lax.scan` over all batches.
        The old loop dispatched one jitted step per batch from Python —
        thousands of dispatches per epoch, each a host round trip to the
        device. One scan = one dispatch per epoch, with
        the lr schedule and RNG folding computed in-graph. `bsz` (the
        pairs-per-batch for the lr schedule) is a traced argument because
        the per-epoch batch shape can differ from epoch to epoch."""
        batch_step = self._make_batch_step()
        lr0 = float(self.learning_rate)
        min_lr = float(self.min_learning_rate)

        def epoch_fn(syn0, syn1, batches, table, hs, pairs0, total_est,
                     bsz, key0):
            def body(carry, batch):
                s0, s1, i = carry
                done = pairs0 + i.astype(jnp.float32) * bsz
                frac = jnp.minimum(1.0, done / total_est)
                lr = jnp.maximum(min_lr, lr0 * (1.0 - frac))
                key = jax.random.fold_in(key0, i)
                s0, s1, loss = batch_step(s0, s1, batch, table, hs, lr, key)
                return (s0, s1, i + 1), loss
            (syn0, syn1, _), losses = jax.lax.scan(
                body, (syn0, syn1, jnp.int32(0)), batches)
            return syn0, syn1, losses.mean()

        return jax.jit(epoch_fn, donate_argnums=(0, 1))

    def fit(self, data) -> "Word2Vec":
        """`data`: iterable of raw strings (tokenized via the factory) or
        pre-tokenized token lists (ref: SentenceIterator /
        SequenceIterator duality)."""
        sentences = _as_sentences(data, self.tokenizer)
        self.vocab.fit(sentences)
        V, D = self.vocab.num_words(), self.layer_size
        rng = np.random.RandomState(self.seed)
        self.syn0 = ((rng.rand(V, D).astype(np.float32) - 0.5) / D)
        pts = cds = cm = None
        if self.use_hs:
            tree = HuffmanTree(self.vocab)
            L = max((len(vw.codes) for vw in self.vocab.words.values()),
                    default=1) or 1
            pts = np.zeros((V, L), np.int32)
            cds = np.zeros((V, L), np.float32)
            cm = np.zeros((V, L), np.float32)
            for w, vw in self.vocab.words.items():
                n = len(vw.codes)
                pts[vw.index, :n] = vw.points
                cds[vw.index, :n] = vw.codes
                cm[vw.index, :n] = 1.0
            # syn1 rows = Huffman INNER nodes, not words
            self.syn1 = np.zeros((max(1, tree.num_inner), D), np.float32)
        else:
            self.syn1 = np.zeros((V, D), np.float32)
        sent_idx = [np.asarray([self.vocab.index_of(t) for t in s
                                if self.vocab.contains_word(t)], np.int64)
                    for s in sentences]
        sent_idx = [s for s in sent_idx if len(s) > 1]
        counts = self.vocab.counts_array()
        total = counts.sum()
        table = jnp.asarray(_neg_table(self.vocab))
        hs = None
        if self.use_hs:
            # Huffman path tables stay device-resident; batches gather
            # from them on device instead of pre-gathering [N_pairs, L]
            # paths on host
            hs = (jnp.asarray(pts), jnp.asarray(cds), jnp.asarray(cm))
        syn0, syn1 = jnp.asarray(self.syn0), jnp.asarray(self.syn1)
        key = jax.random.PRNGKey(self.seed)
        pairs_done = 0
        total_pairs_est = None
        epoch_fn = self._make_epoch_fn()
        for epoch in range(self.epochs):
            ss = self._subsample(sent_idx, counts, total, rng)
            if self.algorithm == "skipgram":
                cols = _gen_pairs(ss, self.window_size, rng)
            else:
                cols = _gen_cbow(ss, self.window_size, rng)
            n = len(cols[0])
            if n == 0:
                continue  # zero-pair epoch (tiny/fully-subsampled corpus)
            perm = rng.permutation(n)
            cols = tuple(c[perm] for c in cols)
            stable = self.epochs * self.iterations > 1
            if total_pairs_est is None:
                # count padded SLOTS, not raw pairs — pairs_done advances
                # by slots, so a raw-pair total would finish the lr decay
                # early by the duplication factor
                total_pairs_est = max(1, _padded_total(
                    n, self.batch_size, stable)) \
                    * self.epochs * self.iterations
            for it in range(self.iterations):
                for batches, B, tot in _iter_example_chunks(
                        cols, self.batch_size, stable):
                    key, sub = jax.random.split(key)
                    syn0, syn1, _ = epoch_fn(
                        syn0, syn1, batches, table, hs,
                        jnp.float32(pairs_done),
                        jnp.float32(total_pairs_est), jnp.float32(B), sub)
                    pairs_done += tot
        self.syn0 = np.asarray(syn0)
        self.syn1 = np.asarray(syn1)
        return self

    # accuracy-style analogy query (ref: WordVectors.wordsNearest with
    # positive/negative lists)
    def words_nearest_sum(self, positive: Sequence[str],
                          negative: Sequence[str] = (),
                          top_n: int = 10) -> List[str]:
        vec = np.zeros(self.layer_size, np.float32)
        for w in positive:
            v = self.word_vector(w)
            if v is not None:
                vec += v
        for w in negative:
            v = self.word_vector(w)
            if v is not None:
                vec -= v
        out = self.words_nearest(vec, top_n + len(positive) + len(negative))
        skip = set(positive) | set(negative)
        return [w for w in out if w not in skip][:top_n]
