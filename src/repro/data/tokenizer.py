"""Deterministic hashing tokenizer (offline stand-in for HF tokenizers).

Splits on whitespace/punctuation; each token maps to a stable
blake2-hashed id.  No vocabulary files, fully reproducible, adequate for
the framework's data-path and training mechanics (the encoder never sees
raw text anyway).

The batch path (``batch_encode_ids`` / ``batch_encode``) maps each
token occurrence through the id cache, with a dict local to the call
behind it, so each distinct token of a batch is hashed at most once
even when the cache is full.  It replaced an ``np.unique`` over an
object array of the batch's tokens: for 16,384 MS MARCO-shaped passages
(910,914 tokens, 46,736 distinct) that sort of Python strings took
1.65 s of a 1.9 s call on a CPU core, held the GIL throughout, and
gave the same ids as the dict lookups, which take about 0.4 s for the
whole call.
"""

from __future__ import annotations

import hashlib
import re
import threading

import numpy as np

_TOKEN_RE = re.compile(r"[a-z0-9]+|[^\sa-z0-9]")
_CACHE_CAP = 1_000_000          # most token ids a tokenizer keeps


class HashTokenizer:
    pad_id = 0
    bos_id = 1
    eos_id = 2
    n_special = 3

    def __init__(self, vocab_size: int = 50304, lowercase: bool = True):
        assert vocab_size > self.n_special
        self.vocab_size = vocab_size
        self.lowercase = lowercase
        self._cache: dict[str, int] = {}
        # batch_encode_ids counters: token occurrences mapped, and
        # blake2 calls made; the memo hit share is 1 - hashed / tokens
        self.stats = {"tokens": 0, "hashed": 0}
        self._stats_lock = threading.Lock()

    def _hash_id(self, tok: str) -> int:
        h = hashlib.blake2b(tok.encode(), digest_size=8).digest()
        return self.n_special + int.from_bytes(h, "little") % (
            self.vocab_size - self.n_special)

    def _token_id(self, tok: str) -> int:
        tid = self._cache.get(tok)
        if tid is None:
            tid = self._hash_id(tok)
            if len(self._cache) < _CACHE_CAP:
                self._cache[tok] = tid
        return tid

    def encode(self, text: str, max_len: int | None = None,
               append_eos: bool = False) -> list[int]:
        if self.lowercase:
            text = text.lower()
        ids = [self._token_id(t) for t in _TOKEN_RE.findall(text)]
        if append_eos:
            ids.append(self.eos_id)
        if max_len is not None:
            ids = ids[:max_len]
            # truncation may leave nothing to overwrite (max_len == 0 or
            # an empty text): only re-pin the eos on a non-empty tail
            if append_eos and ids and ids[-1] != self.eos_id:
                ids[-1] = self.eos_id
        return ids

    def batch_encode_ids(self, texts: list[str],
                         max_len: int | None = None,
                         append_eos: bool = False) -> list[list[int]]:
        """Tokenize a batch; hash each distinct token at most once.

        Returns exactly ``[self.encode(t, max_len, append_eos) for t in
        texts]`` (the scalar path is the semantic reference).  Tokens
        past the cut are never mapped; with ``append_eos`` the eos takes
        the last of ``max_len`` places.
        """
        cache = self._cache
        lookup = cache.get
        batch: dict[str, int] = {}     # ids hashed in this call
        eos = append_eos and max_len != 0
        keep = max_len - 1 if append_eos and max_len else max_len
        n_tokens = n_hashed = 0
        out: list[list[int]] = []
        for text in texts:
            toks = _TOKEN_RE.findall(text.lower() if self.lowercase
                                     else text)[:keep]
            ids = list(map(lookup, toks))
            if None in ids:
                for i, tid in enumerate(ids):
                    if tid is None:
                        tok = toks[i]
                        tid = batch.get(tok)
                        if tid is None:
                            tid = batch[tok] = self._hash_id(tok)
                            n_hashed += 1
                            if len(cache) < _CACHE_CAP:
                                cache[tok] = tid
                        ids[i] = tid
            n_tokens += len(ids)
            if eos:
                ids.append(self.eos_id)
            out.append(ids)
        with self._stats_lock:
            self.stats["tokens"] += n_tokens
            self.stats["hashed"] += n_hashed
        return out

    def batch_encode(self, texts: list[str], max_len: int,
                     append_eos: bool = False,
                     pad_to_multiple: int = 1):
        """Returns (tokens (B, L) int32, mask (B, L) int32)."""
        enc = self.batch_encode_ids(texts, max_len, append_eos)
        longest = max((len(e) for e in enc), default=1)
        longest = max(longest, 1)
        if pad_to_multiple > 1:
            longest = -(-longest // pad_to_multiple) * pad_to_multiple
        longest = min(longest, max_len) if max_len else longest
        return pad_token_rows(enc, longest, self.pad_id)


def pad_token_rows(rows: list[list[int]], length: int, pad_id: int = 0,
                   n_rows: int | None = None):
    """Stack ragged id rows into ((B, L) tokens, (B, L) mask) int32.

    ``n_rows`` > len(rows) appends all-pad rows (mask 0) — the encode
    pipeline's fixed-batch-dim ragged tail.  Rows longer than ``length``
    are truncated.
    """
    b = len(rows) if n_rows is None else n_rows
    length = max(length, 1)
    toks = np.full((b, length), pad_id, np.int32)
    mask = np.zeros((b, length), np.int32)
    for i, e in enumerate(rows):
        e = e[:length]
        toks[i, : len(e)] = e
        mask[i, : len(e)] = 1
    return toks, mask
