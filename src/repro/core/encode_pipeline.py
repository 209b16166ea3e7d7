"""Recompile-free bucketed encode pipeline (paper §3.5 "no overhead").

The online-regime encoder wall has three parts, each addressed here:

  * **shape churn** — padding every batch to its own longest length
    compiles one XLA executable per distinct ``(B, L)`` shape, so a
    varied-length corpus compiles O(corpus / batch) times.  The pipeline
    sorts texts by token length and pads each fixed-batch-dim batch to
    the smallest rung of a geometric **bucket ladder**
    (:func:`bucket_ladder`), so total encoder compiles are bounded by
    the ladder size, and padding FLOPs track the text lengths instead of
    the per-batch maximum.  The original text order is restored on
    output — bucketing is invisible to callers.
  * **serial host tokenization** — :meth:`EncodePipeline.stream`
    tokenizes up to ``encode_pipeline_depth`` windows ahead of the
    device encode stage (bounded queue), so host tokenization overlaps
    device compute; each call runs :meth:`HashTokenizer.
    batch_encode_ids` (unique-token ``np.unique`` path) fanned over a
    ``tokenizer_workers`` pool — the fan-out parallelizes GIL-releasing
    tokenizers (e.g. duck-typed Rust HF tokenizers); for the
    pure-Python GIL-bound HashTokenizer the overlap is the win.
  * **host round-trips** — the jitted encode step donates its token
    buffers (accelerator backends; CPU skips the no-op donation) and
    its output can stay device-resident
    (``device=True``), flowing straight into
    ``ShardedSearchDriver``'s superchunk executor via
    :class:`PipelineChunkSource` (the driver's pull-based
    ``open_slice`` chunk-source contract) with no d2h+h2d per chunk.

Rankings are unchanged: bucketing only regroups rows and pads with
exactly-masked zeros, and every batch row is encoded independently.
"""

from __future__ import annotations

from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.data.tokenizer import HashTokenizer, pad_token_rows


def bucket_ladder(max_len: int, n_buckets: int = 6,
                  multiple: int = 8) -> tuple[int, ...]:
    """Geometric padded-length ladder: ``multiple`` ... ``max_len``.

    Rungs are multiples of ``multiple`` (SIMD/sublane alignment — also
    why padding with exact zeros keeps reductions bitwise stable across
    rungs), strictly increasing, and the top rung is exactly
    ``max_len`` (the tokenizer truncates there, so longer pads are
    waste).  At most ``n_buckets`` rungs; duplicates from rounding
    collapse.
    """
    max_len = max(int(max_len), 1)
    multiple = max(int(multiple), 1)
    if n_buckets <= 1 or max_len <= multiple:
        return (max_len,)
    rungs = []
    for i in range(n_buckets):
        frac = (max_len / multiple) ** (i / (n_buckets - 1))
        rung = -(-int(round(multiple * frac)) // multiple) * multiple
        rungs.append(min(rung, max_len))
    rungs[-1] = max_len
    return tuple(sorted(set(rungs)))


class EncodePipeline:
    """Parallel tokenize -> shape-bucketed batches -> donated jit encode.

    Parameters
    ----------
    encode_fn : ``(params, {"tokens", "mask"}) -> (B, d)`` pure encoder.
    tokenizer : :class:`HashTokenizer` (or duck-type with
        ``batch_encode_ids`` and ``pad_id``).
    append_eos / pad_to_multiple : collator tokenization settings.
    buckets : ladder rung count (compile bound per ``max_len``).
    batch_size : fixed batch dim; ragged tails pad up with masked rows.
    tokenizer_workers : host tokenization threads (<=1 = inline).
    depth : windows tokenized ahead of device encode in
        :meth:`stream` (0 = synchronous).
    """

    def __init__(self, encode_fn: Callable, tokenizer: HashTokenizer, *,
                 append_eos: bool = False, pad_to_multiple: int = 8,
                 buckets: int = 6, batch_size: int = 32,
                 tokenizer_workers: int = 2, depth: int = 2):
        self.tokenizer = tokenizer
        self.append_eos = append_eos
        self.pad_to_multiple = max(pad_to_multiple, 1)
        self.buckets = buckets
        self.batch_size = max(batch_size, 1)
        self.tokenizer_workers = max(tokenizer_workers, 1)
        self.depth = max(depth, 0)
        self.stats = {"compiles": 0, "tokens_real": 0, "tokens_padded": 0}
        self._ladders: dict[int, tuple[int, ...]] = {}

        def _traced(params, tokens, mask):
            # trace-time side effect: runs once per (B, L) shape — the
            # real compile count, not a proxy
            self.stats["compiles"] += 1
            return encode_fn(params, {"tokens": tokens, "mask": mask})

        # donate the token buffers so accelerator backends can release
        # them for reuse mid-computation; on CPU an int32 (B, L) buffer
        # can never serve the float32 (B, d) output, so donation is pure
        # warning noise — skip it
        donate = () if jax.default_backend() == "cpu" else (1, 2)
        self._jit = jax.jit(_traced, donate_argnums=donate)

    # -- stage 1: host tokenization -------------------------------------------
    def tokenize(self, texts: Sequence[str], max_len: int,
                 fmt: Callable[[str], str] | None = None
                 ) -> list[list[int]]:
        """Token-id rows for ``texts``, fanned over the tokenizer pool."""
        with tracing.span("trove.encode.tokenize", n=len(texts)):
            texts = ([fmt(t) for t in texts] if fmt is not None
                     else list(texts))
            if (self.tokenizer_workers <= 1
                    or len(texts) < 4 * self.tokenizer_workers):
                return self.tokenizer.batch_encode_ids(texts, max_len,
                                                       self.append_eos)
            step = -(-len(texts) // self.tokenizer_workers)
            # a per-call pool (like stream()'s tokenize-ahead pool): spawn
            # cost is microseconds against a window of tokenization, and
            # no idle threads outlive the call
            with ThreadPoolExecutor(self.tokenizer_workers,
                                    thread_name_prefix="tokenize") as pool:
                parts = list(pool.map(
                    lambda lo: self.tokenizer.batch_encode_ids(
                        texts[lo: lo + step], max_len, self.append_eos),
                    range(0, len(texts), step)))
            return [row for part in parts for row in part]

    # -- stage 2: shape bucketing ---------------------------------------------
    def ladder(self, max_len: int) -> tuple[int, ...]:
        lad = self._ladders.get(max_len)
        if lad is None:
            lad = bucket_ladder(max_len, self.buckets, self.pad_to_multiple)
            self._ladders[max_len] = lad
        return lad

    def _fit(self, length: int, ladder: tuple[int, ...]) -> int:
        for rung in ladder:
            if rung >= length:
                return rung
        return ladder[-1]

    def _batch_dim(self, n: int, batch_size: int,
                   min_batch: int = 8) -> int:
        """Fixed batch dim: ``batch_size`` once the input covers it; a
        power-of-two below it for one-shot small inputs (still a bounded
        shape set — log2(batch_size) dims at most).  ``min_batch`` is the
        floor of that power-of-two ladder: the serve frontend passes 1 so
        a deadline-flushed single query encodes as (1, L) instead of
        padding to (8, L) — batch rows beyond ``n`` are exact-zero
        masked either way, so the choice never changes output rows."""
        if n >= batch_size:
            return batch_size
        b = max(1, min(min_batch, batch_size))
        while b < n:
            b <<= 1
        return min(b, batch_size)

    # -- stage 3: donated device encode ---------------------------------------
    def _encode_window(self, params, enc: list[list[int]], max_len: int,
                       device: bool, batch_size: int,
                       min_batch_dim: int = 8):
        """Encode one window of token rows; output rows restored to the
        window's original order (device- or host-resident)."""
        n = len(enc)
        if n == 0:
            return (jnp.empty((0, 0), jnp.float32) if device
                    else np.empty((0, 0), np.float32))
        ladder = self.ladder(max_len)
        b = self._batch_dim(n, batch_size, min_batch_dim)
        lengths = np.fromiter((len(e) for e in enc), np.int64, count=n)
        order = np.argsort(lengths, kind="stable")
        parts, perm = [], []
        for lo in range(0, n, b):
            idx = order[lo: lo + b]
            rung = self._fit(max(lengths[idx].max(), 1), ladder)
            with tracing.span("trove.encode.run", rung=rung):
                toks, mask = pad_token_rows([enc[i] for i in idx], rung,
                                            self.tokenizer.pad_id, n_rows=b)
                out = self._jit(params, toks, mask)
            parts.append(out[: len(idx)])
            perm.append(idx)
            self.stats["tokens_real"] += int(lengths[idx].sum())
            self.stats["tokens_padded"] += b * rung
        inverse = np.empty(n, np.int64)
        inverse[np.concatenate(perm)] = np.arange(n)
        if device:
            return jnp.concatenate(parts)[jnp.asarray(inverse)]
        with tracing.span("trove.encode.fetch"):
            return np.concatenate([np.asarray(p) for p in parts])[inverse]

    # -- public API -----------------------------------------------------------
    def encode(self, params, texts: Sequence[str], max_len: int, *,
               fmt: Callable[[str], str] | None = None,
               device: bool = False, batch_size: int | None = None,
               min_batch_dim: int = 8):
        """One-shot ordered encode of ``texts`` -> (N, d).

        ``min_batch_dim`` floors the power-of-two batch-dim ladder for
        inputs smaller than ``batch_size`` (see :meth:`_batch_dim`); the
        serve frontend passes 1 to keep single-query micro-batch latency
        proportional to one row, not eight."""
        enc = self.tokenize(texts, max_len, fmt)
        return self._encode_window(params, enc, max_len, device,
                                   batch_size or self.batch_size,
                                   min_batch_dim)

    def stream(self, params, texts: Sequence[str], *, lo: int, hi: int,
               chunk_size: int, max_len: int,
               fmt: Callable[[str], str] | None = None,
               device: bool = False):
        """Yield ``(offset, (chunk, d) embeddings)`` over ``texts[lo:hi)``
        in original order, ``chunk_size`` rows at a time.

        Texts are processed in windows (several chunks each, so length
        sorting has room to work); window ``w + 1`` tokenizes on a
        background thread while window ``w`` encodes on device — the
        bounded-queue host/device overlap, ``depth`` windows deep.
        """
        window = max(chunk_size, self.batch_size) * 8
        spans = [(s, min(s + window, hi)) for s in range(lo, hi, window)]
        if not spans:
            return

        def tok(span):
            return self.tokenize(texts[span[0]: span[1]], max_len, fmt)

        def emit(span, enc):
            ws, we = span
            embs = self._encode_window(params, enc, max_len, device,
                                       self.batch_size)
            for off in range(ws, we, chunk_size):
                yield off, embs[off - ws: min(off - ws + chunk_size,
                                              we - ws)]

        if self.depth == 0 or len(spans) == 1:
            for span in spans:
                yield from emit(span, tok(span))
            return
        with ThreadPoolExecutor(self.depth,
                                thread_name_prefix="tokenize-ahead") as ex:
            pending = deque(ex.submit(tok, span)
                            for span in spans[: self.depth])
            for i, span in enumerate(spans):
                enc = pending.popleft().result()
                if self.depth + i < len(spans):
                    pending.append(ex.submit(tok, spans[self.depth + i]))
                yield from emit(span, enc)

    def jit_cache_size(self) -> int:
        """Compiled-executable count straight from jax."""
        return self._jit._cache_size()


class PipelineChunkSource:
    """Pull-based pipeline view for ``ShardedSearchDriver``.

    The driver duck-types its ``load_chunk`` argument: an object with
    ``open_slice(lo, hi, chunk_size)`` is asked for an ordered
    ``(offset, embeddings)`` iterator over its shard slice — the
    pipeline keeps tokenization overlapped behind the scenes and
    (``device=True``) hands back device-resident chunks that the
    superchunk executor stacks without a host round-trip.
    """

    def __init__(self, pipeline: EncodePipeline, params,
                 texts: Sequence[str], max_len: int, *,
                 fmt: Callable[[str], str] | None = None,
                 device: bool = False):
        self.pipeline = pipeline
        self.params = params
        self.texts = texts
        self.max_len = max_len
        self.fmt = fmt
        self.device = device

    def open_slice(self, lo: int, hi: int, chunk_size: int):
        return self.pipeline.stream(
            self.params, self.texts, lo=lo, hi=hi, chunk_size=chunk_size,
            max_len=self.max_len, fmt=self.fmt, device=self.device)
