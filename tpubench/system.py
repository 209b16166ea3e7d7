"""The system under test, built from a configuration file.

The program is built through its public entry points: its encoder
configuration, ``RetrievalEvaluator`` with the deployment's
``EvaluationArguments``, and the ``EmbeddingCache`` the corpus vectors
are stored in.  The weights are not the program's: they are made from
the seed (``reference.make_params``) in the program's layout, so the
reference can take the same ones.
"""

from __future__ import annotations

import os

import numpy as np

from tpubench import reference, textgen

ARCH_KEYS = {
    # configuration key -> the program's LMConfig field
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
}


def encoder_numbers(cfg: dict) -> dict:
    """The encoder as the reference and the work counts read it."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"n_layers": cfg["num_hidden_layers"], "d_model": d,
            "n_heads": h, "head_dim": d // h, "d_ff": cfg["intermediate_size"],
            "vocab_size": cfg["vocab_size"],
            "layernorm_eps": cfg["layer_norm_eps"],
            "rope_theta": cfg["rope_theta"]}


class System:
    """Evaluator, weights and helpers for one configuration and seed."""

    def __init__(self, cfg: dict, seed: int):
        import jax.numpy as jnp

        from repro.core.collator import RetrievalCollator
        from repro.core.config import DataArguments, EvaluationArguments
        from repro.core.evaluator import RetrievalEvaluator
        from repro.data.tokenizer import HashTokenizer
        from repro.models.encoder import DefaultEncoder
        from repro.models.retriever import BiEncoderRetriever
        from repro.models.transformer import LMConfig, abstract_params

        self.cfg = cfg
        self.seed = seed
        self.enc = encoder_numbers(cfg)
        fields = {ARCH_KEYS[k]: cfg[k] for k in ARCH_KEYS}
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        self.lm = LMConfig(
            name=cfg["name"], n_kv_heads=h, head_dim=d // h,
            activation="gelu", norm="layernorm", pooling=cfg["pooling"],
            dtype=jnp.dtype(cfg["torch_dtype"]), **fields)
        self.params = reference.make_params(abstract_params(self.lm), seed)
        data = DataArguments(vocab_size=cfg["vocab_size"],
                             query_max_len=cfg["query_max_len"],
                             passage_max_len=cfg["passage_max_len"])
        retriever = BiEncoderRetriever(DefaultEncoder(self.lm), "infonce")
        collator = RetrievalCollator(data, HashTokenizer(cfg["vocab_size"]))
        self.args = EvaluationArguments(**cfg["evaluation"])
        self.ev = RetrievalEvaluator(self.args, retriever, collator,
                                     self.params)

    def cache(self, work_dir: str, name: str):
        from repro.core.embedding_cache import EmbeddingCache
        return EmbeddingCache(os.path.join(work_dir, name),
                              dim=self.cfg["hidden_size"],
                              dtype=np.dtype(self.cfg["storage_dtype"]))

    def anchors(self):
        """Reference vectors of seeded passages: the centres the corpus
        vectors are drawn around."""
        import jax.numpy as jnp
        spec = self.cfg["corpus_vectors"]
        text = self.cfg["text"]
        texts = textgen.make_texts(spec["anchors"], text["passage_words"],
                                   text["words"], self.seed, "anchor")
        vecs = reference.encode_texts(self.params, texts, self.enc,
                                      spec["anchor_tokens"],
                                      precision="default", block=1024)
        return jnp.asarray(vecs)

    def corpus_vectors(self, anchors):
        """The corpus on the device, in the storage dtype."""
        spec = self.cfg["corpus_vectors"]
        return reference.make_corpus(anchors, self.cfg["num_passages"],
                                     spec["noise"], self.seed,
                                     self.cfg["storage_dtype"])
