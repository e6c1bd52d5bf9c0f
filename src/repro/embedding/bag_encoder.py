"""Frozen bag-of-word-vectors encoder (the production router's e(.), §5.5).

Stands in for all-MiniLM-L6-v2: mean-pool word vectors, L2-normalize. The
encoder is deliberately *frozen* — OATS-S1 changes only the stored tool
vectors, never the encoder (paper §4.1), and OATS-S3 composes a trainable
adapter head on top of this encoder's output (paper §4.3).

Both a ragged (list-of-token-arrays) numpy path — the gateway's embed phase
and the offline benchmark/evaluation code — and a padded jnp path (jittable,
for training code) are provided; they agree to float32 rounding.
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.embedding.vocab import Vocab

__all__ = ["BagEncoder"]

# sorted rows per accumulation block of `BagEncoder.encode`
_BLOCK = 256


class BagEncoder:
    def __init__(self, vocab: Vocab):
        self.vocab = vocab
        self.word_vecs = vocab.word_vecs  # [V, 384] float32
        self._word_vecs_j = jnp.asarray(self.word_vecs)

    @property
    def dim(self) -> int:
        return self.word_vecs.shape[1]

    # ---- ragged numpy path (offline and serving) ------------------------
    def encode(self, token_lists: Sequence[np.ndarray]) -> np.ndarray:
        """[B, D] unit means of each list's word vectors; an empty list gives 0.

        One batched pass whose rows are bit-identical to encoding each list
        alone as ``v = word_vecs[toks].mean(0); v / max(norm(v), 1e-9)``:

        - sums: ``mean`` adds a list's rows in token order in float32. The
          rows, sorted by descending length, are accumulated the same way,
          position by position, each position adding to the rows still open
          there; once one row is left it is finished with one sequential
          ``add.reduce`` (what ``mean`` itself runs). Nothing is padded, and a
          long list next to short ones costs its own tokens;
        - the mean divides by the length in the sum's dtype (``mean`` divides
          by an integer count; the quotient rounds to the same value);
        - norms: ``linalg.norm`` of a vector is ``sqrt(dot(v, v))``; ``matmul``
          of a ``[1, D]`` row by a ``[D, 1]`` column runs that same dot, once
          per row, with no Python loop.

        Sorted rows are taken in blocks of `_BLOCK`, so the accumulator stays
        in cache and a large offline batch needs one block of scratch.
        """
        n = len(token_lists)
        out = np.zeros((n, self.dim), dtype=np.float32)
        lengths = np.fromiter(map(len, token_lists), np.intp, count=n)
        order = np.argsort(-lengths, kind="stable")[: np.count_nonzero(lengths)]
        if not len(order):
            return out
        ln_all = lengths[order]
        flat = np.concatenate([token_lists[i] for i in order.tolist()], dtype=np.intp)
        starts_all = np.cumsum(ln_all) - ln_all
        wv = self.word_vecs
        for b0 in range(0, len(order), _BLOCK):
            ln = ln_all[b0 : b0 + _BLOCK]
            st = starts_all[b0 : b0 + _BLOCK]
            acc = wv[flat[st]]
            # positions open on two or more rows: ln[1] is the second longest
            shared = int(ln[1]) if len(ln) > 1 else 1
            open_rows = np.searchsorted(-ln, -np.arange(1, shared), side="left")
            for pos, n_open in enumerate(open_rows.tolist(), start=1):
                acc[:n_open] += wv[flat[st[:n_open] + pos]]
            if ln[0] > shared:
                # the longest row alone: its partial sum, then its later rows
                rest = wv[flat[st[0] + shared - 1 : st[0] + ln[0]]]
                rest[0] = acc[0]
                acc[0] = np.add.reduce(rest, axis=0)
            acc /= ln[:, None].astype(acc.dtype)
            norm = np.sqrt(np.matmul(acc[:, None, :], acc[:, :, None]).reshape(-1))
            acc /= np.maximum(norm, np.float32(1e-9))[:, None]
            out[order[b0 : b0 + _BLOCK]] = acc
        return out

    def encode_one(self, tokens: np.ndarray) -> np.ndarray:
        return self.encode([tokens])[0]

    # ---- padded jnp path (jittable, used in the serving hot path) -------
    def encode_padded(self, ids: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
        """ids: [B, L] int32, mask: [B, L] {0,1}. Returns [B, 384] unit rows."""
        vecs = jnp.take(self._word_vecs_j, ids, axis=0)  # [B, L, D]
        m = mask[..., None].astype(vecs.dtype)
        summed = (vecs * m).sum(axis=1)
        count = jnp.maximum(m.sum(axis=1), 1.0)
        mean = summed / count
        norm = jnp.maximum(jnp.linalg.norm(mean, axis=-1, keepdims=True), 1e-9)
        return mean / norm


def pad_token_lists(
    token_lists: Sequence[np.ndarray], max_len: int | None = None
) -> tuple[np.ndarray, np.ndarray]:
    """Pack ragged token lists into (ids, mask) for the padded path."""
    if max_len is None:
        max_len = max((len(t) for t in token_lists), default=1)
        max_len = max(max_len, 1)
    ids = np.zeros((len(token_lists), max_len), dtype=np.int32)
    mask = np.zeros((len(token_lists), max_len), dtype=np.int32)
    for i, toks in enumerate(token_lists):
        n = min(len(toks), max_len)
        ids[i, :n] = np.asarray(toks)[:n]
        mask[i, :n] = 1
    return ids, mask
