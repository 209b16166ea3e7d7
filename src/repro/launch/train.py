"""End-to-end training driver.

Single script, three scales — exactly the paper's "same script, any
number of nodes" posture:
  * CPU/dev:      python -m repro.launch.train --arch trove-base --smoke
  * single pod:   launched under a TPU runtime; mesh (16,16)
  * multi-pod:    --multi-pod; mesh (2,16,16); jax.distributed handles
                  process bootstrap (one process per host)

Builds the synthetic-or-real retrieval dataset via MaterializedQRel, a
BiEncoderRetriever on the selected --arch backbone, and runs
RetrievalTrainer (grad accumulation, async checkpoints, fault tolerance).
"""

from __future__ import annotations

import os


def main(argv=None):
    import jax

    from repro.core.collator import RetrievalCollator
    from repro.core.config import (DataArguments, MaterializedQRelConfig,
                                   ModelArguments,
                                   RetrievalTrainingArguments, parse_cli)
    from repro.core.datasets import BinaryDataset
    from repro.core.metrics import IRMetrics
    from repro.configs import get_arch
    from repro.data.synthetic import make_retrieval_dataset
    from repro.data.tokenizer import HashTokenizer
    from repro.models.encoder import DefaultEncoder
    from repro.models.retriever import BiEncoderRetriever
    from repro.training.trainer import RetrievalTrainer

    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="trove-base")
    ap.add_argument("--data-dir", default="/tmp/trove_data")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + synthetic data (CPU)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mesh", default="local",
                    choices=["local", "pod", "multipod"])
    args, rest = ap.parse_known_args(argv)

    train_args, model_args, data_args = parse_cli(
        RetrievalTrainingArguments, ModelArguments, DataArguments,
        argv=rest)

    arch = get_arch(args.arch)
    if args.smoke:
        arch = arch.reduced()
        import dataclasses
        arch = arch.variant(dtype=jax.numpy.float32) \
            if hasattr(arch, "variant") else arch
    assert arch.family == "lm", "train.py drives LM retrieval encoders"

    if not os.path.exists(os.path.join(args.data_dir, "queries.jsonl")):
        make_retrieval_dataset(args.data_dir, n_queries=256, n_docs=2048,
                               n_topics=64)

    mesh = None
    if args.mesh == "pod" or args.multi_pod or args.mesh == "multipod":
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(
            multi_pod=args.multi_pod or args.mesh == "multipod")

    tok = HashTokenizer(arch.cfg.vocab_size)
    data_args.vocab_size = arch.cfg.vocab_size
    retriever = BiEncoderRetriever.from_model_args(
        model_args, arch.cfg, encoder=DefaultEncoder(arch.cfg))
    collator = RetrievalCollator(data_args, tok)
    pos = MaterializedQRelConfig(
        min_score=1,
        qrel_path=os.path.join(args.data_dir, "qrels", "train.tsv"),
        query_path=os.path.join(args.data_dir, "queries.jsonl"),
        corpus_path=os.path.join(args.data_dir, "corpus.jsonl"))
    dataset = BinaryDataset(
        data_args, retriever.format_query, retriever.format_passage,
        pos, pos, cache_root=os.path.join(args.data_dir, "cache"))

    trainer = RetrievalTrainer(
        retriever, train_args, collator, dataset, mesh=mesh,
        dev_dataset=None, compute_metrics=IRMetrics())
    state = trainer.train()
    for rec in trainer.logs:
        print(rec)
    print(f"done at step {int(state['step'])}; "
          f"checkpoints in {train_args.output_dir}/checkpoints")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
