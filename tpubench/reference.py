"""The plain reference, and the weights and vectors both sides are given.

Nothing here imports the program.  The encoder is written out in
float32 ``jax.numpy`` from the configuration's numbers, following the
architecture the configuration states (pre-norm LayerNorm blocks, RoPE,
causal mask, tanh GELU, mean pooling, L2 normalisation).  Scoring is
exact: ``q @ C.T`` at ``Precision.HIGHEST`` then ``lax.top_k``.

``precision`` selects the arithmetic of the forward and of the scan:
``"float32"`` is the reference (every matmul at ``HIGHEST``);
``"float8"`` rounds every matmul input to float8_e4m3 first (the
control, one step below the configuration's bfloat16 encoder and
float16 corpus); ``"default"`` is the backend's default matmul
precision, used only to make corpus anchors.

Weights and corpus vectors are made here from the seed, on the device,
each in one jitted call, and handed to the program and to the
reference alike.
"""

from __future__ import annotations

import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_TOKEN_RE = re.compile(r"[a-z0-9]+|[^\sa-z0-9]")
N_SPECIAL = 3          # pad, bos, eos


# -- tokenizer (the configuration's hashing tokenizer, written out) ---------


def tokenize(texts: list[str], vocab: int, max_len: int
             ) -> tuple[np.ndarray, np.ndarray]:
    """Lower-cased word/punctuation tokens, each id ``3 + blake2b-64(tok)
    mod (vocab - 3)``, truncated at ``max_len`` and right-padded with 0.
    Returns ``(tokens, mask)`` int32 of shape ``(len(texts), max_len)``."""
    toks = np.zeros((len(texts), max_len), np.int32)
    mask = np.zeros((len(texts), max_len), np.int32)
    memo: dict[str, int] = {}
    for r, text in enumerate(texts):
        words = _TOKEN_RE.findall(text.lower())[:max_len]
        for c, w in enumerate(words):
            tid = memo.get(w)
            if tid is None:
                h = hashlib.blake2b(w.encode(), digest_size=8).digest()
                tid = N_SPECIAL + int.from_bytes(h, "little") % (
                    vocab - N_SPECIAL)
                memo[w] = tid
            toks[r, c] = tid
        mask[r, :len(words)] = 1
    return toks, mask


# -- weights and vectors from the seed -----------------------------------------


def jax_seed(seed: int) -> int:
    """A 31-bit JAX key seed derived from any non-negative seed."""
    return int(np.random.default_rng([int(seed), 7]).integers(0, 2**31 - 1))


def make_params(abstract, seed: int):
    """Weights in the program's layout (``abstract``: a pytree of
    ``ShapeDtypeStruct``), in one jitted call: norm scales 1, biases 0,
    every other leaf ``N(0, 0.02)`` in its stored dtype."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def name(path):
        last = path[-1]
        return getattr(last, "key", str(last))

    names = [name(p) for p, _ in paths]
    leaves = [l for _, l in paths]

    @jax.jit
    def build(key):
        out = []
        for i, (nm, leaf) in enumerate(zip(names, leaves)):
            if nm.startswith(("ln", "final_ln")) and not nm.endswith("_b"):
                out.append(jnp.ones(leaf.shape, leaf.dtype))
            elif nm.endswith("_b") or nm.startswith("b"):
                out.append(jnp.zeros(leaf.shape, leaf.dtype))
            else:
                k = jax.random.fold_in(key, i)
                out.append((0.02 * jax.random.normal(k, leaf.shape, jnp.float32)
                            ).astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return build(jax.random.key(jax_seed(seed)))


def make_corpus(anchors: jax.Array, n_rows: int, noise: float, seed: int,
                dtype=jnp.float16) -> jax.Array:
    """``n_rows`` unit vectors, row ``i`` drawn around anchor
    ``a_i ~ U(anchors)``: ``normalize(a_i + noise * u_i)`` with ``u_i``
    a random unit vector, cast to ``dtype`` (what the cache stores).
    One jitted call; the same seed gives the same rows."""
    return _make_corpus(anchors, jax.random.key(jax_seed(seed) ^ 0x5EED),
                        n_rows, float(noise), jnp.dtype(dtype))


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _make_corpus(anchors, key, n_rows, noise, dtype):
    block = min(n_rows, 1 << 16)          # bounds the float32 temporaries
    n_blocks = -(-n_rows // block)

    def one(b):
        ka, ku = jax.random.split(jax.random.fold_in(key, b))
        pick = jax.random.randint(ka, (block,), 0, anchors.shape[0])
        u = jax.random.normal(ku, (block, anchors.shape[1]), jnp.float32)
        u = u / jnp.linalg.norm(u, axis=1, keepdims=True)
        x = anchors[pick] + noise * u
        x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
        return x.astype(dtype)

    out = jax.lax.map(one, jnp.arange(n_blocks))
    return out.reshape(n_blocks * block, -1)[:n_rows]


# -- the plain forward ----------------------------------------------------------


def _round(x, precision: str):
    if precision == "float8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(eq: str, a, b, precision: str):
    return jnp.einsum(eq, _round(a, precision), _round(b, precision),
                      precision=None if precision == "default" else HIGHEST)


def _layernorm(x, scale, bias, eps: float):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _rope(x, theta: float):
    """x: (B, S, H, hd); rotate the two halves of each head."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = np.exp(-np.log(theta) * np.arange(half) / half)
    ang = np.arange(s)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("arch", "precision"))
def encode(params, tokens, mask, *, arch: tuple, precision: str = "float32"):
    """(B, S) tokens -> (B, d) unit vectors, the configuration's forward.

    ``arch`` is ``(n_layers, n_heads, head_dim, eps, rope_theta)``."""
    n_layers, n_heads, head_dim, eps, theta = arch
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x = f32(params["embed"])[tokens]
    s = tokens.shape[1]
    allowed = (jnp.tril(jnp.ones((s, s), bool))[None]
               & (mask[:, None, :] > 0))
    blocks = params["blocks"]
    for layer in range(n_layers):
        p = {k: f32(v[layer]) for k, v in blocks.items()}
        h = _layernorm(x, p["ln1"], p["ln1_b"], eps)
        q = _rope(_mm("bsd,dhk->bshk", h, p["wq"], precision), theta)
        k = _rope(_mm("bsd,dhk->bshk", h, p["wk"], precision), theta)
        v = _mm("bsd,dhk->bshk", h, p["wv"], precision)
        att = _mm("bqhk,bshk->bhqs", q, k, precision) / np.sqrt(head_dim)
        att = jnp.where(allowed[:, None], att, -1e30)
        att = jax.nn.softmax(att, axis=-1)
        o = _mm("bhqs,bshk->bqhk", att, v, precision)
        x = x + _mm("bqhk,hkd->bqd", o, p["wo"], precision)
        h = _layernorm(x, p["ln2"], p["ln2_b"], eps)
        x = x + _mm("bsf,fd->bsd",
                    _gelu(_mm("bsd,df->bsf", h, p["wi_up"], precision)),
                    p["wo_ffn"], precision)
    x = _layernorm(x, f32(params["final_ln"]), f32(params["final_ln_b"]), eps)
    m = mask.astype(jnp.float32)[..., None]
    pooled = (x * m).sum(1) / jnp.maximum(m.sum(1), 1e-6)
    return pooled / jnp.maximum(
        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)


def arch_of(encoder_cfg: dict) -> tuple:
    return (encoder_cfg["n_layers"], encoder_cfg["n_heads"],
            encoder_cfg["head_dim"], encoder_cfg["layernorm_eps"],
            encoder_cfg["rope_theta"])


def encode_texts(params, texts: list[str], encoder_cfg: dict, max_len: int,
                 precision: str = "float32", block: int = 256) -> np.ndarray:
    """Reference vectors of ``texts`` (host float32), ``block`` rows per
    call, each padded to ``max_len`` so one program serves every block."""
    toks, mask = tokenize(texts, encoder_cfg["vocab_size"], max_len)
    out = []
    arch = arch_of(encoder_cfg)
    for lo in range(0, len(texts), block):
        t, m = toks[lo:lo + block], mask[lo:lo + block]
        pad = block - len(t)
        if pad:
            t = np.pad(t, ((0, pad), (0, 0)))
            m = np.pad(m, ((0, pad), (0, 0)))
        out.append(np.asarray(encode(params, t, m, arch=arch,
                                     precision=precision))[:block - pad])
    return np.concatenate(out) if out else np.zeros((0, 0), np.float32)


# -- exact scoring ----------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def _block_topk(q, block, offset, *, k: int, precision: str):
    s = jnp.einsum("qd,nd->qn", _round(q, precision),
                   _round(block.astype(jnp.float32), precision),
                   precision=HIGHEST)
    v, i = jax.lax.top_k(s, k)
    return v, i + offset


def exact_topk(q: np.ndarray, corpus: jax.Array, k: int,
               precision: str = "float32", block: int = 1 << 16
               ) -> tuple[np.ndarray, np.ndarray]:
    """Top-``k`` (scores, row indices) of ``q @ corpus.T`` over the
    device corpus, ``block`` rows at a time."""
    qj = jnp.asarray(q, jnp.float32)
    best_v = best_i = None
    n = corpus.shape[0]
    for lo in range(0, n, block):
        blk = corpus[lo:lo + block]
        if blk.shape[0] < block:
            blk = jnp.pad(blk, ((0, block - blk.shape[0]), (0, 0)))
            v, i = _block_topk(qj, blk, lo, k=k, precision=precision)
            valid = i < n
            v = jnp.where(valid, v, -jnp.inf)
        else:
            v, i = _block_topk(qj, blk, lo, k=k, precision=precision)
        if best_v is None:
            best_v, best_i = v, i
        else:
            cv = jnp.concatenate([best_v, v], 1)
            ci = jnp.concatenate([best_i, i], 1)
            best_v, pos = jax.lax.top_k(cv, k)
            best_i = jnp.take_along_axis(ci, pos, 1)
    return np.asarray(best_v), np.asarray(best_i)


def scores_of(q: np.ndarray, corpus: jax.Array, rows: np.ndarray
              ) -> np.ndarray:
    """Exact float32 scores ``q[r] . corpus[rows[r, j]]``; rows < 0 give
    ``-inf``."""
    safe = np.clip(rows, 0, corpus.shape[0] - 1)
    vecs = corpus[jnp.asarray(safe)].astype(jnp.float32)      # (Q, k, d)
    s = jnp.einsum("qd,qkd->qk", jnp.asarray(q, jnp.float32), vecs,
                   precision=HIGHEST)
    return np.where(rows >= 0, np.asarray(s), -np.inf)
