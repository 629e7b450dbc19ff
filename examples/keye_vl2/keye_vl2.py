#!/usr/bin/env python3
"""KEYEVL2 (Keye-VL-2.0's language model: a decoder language model on the graph path) at a small size.

Tokens are nodes, documents are graphs, a packed batch is a packed sequence:
the same ``run_training`` entry, loader, step and optimizer as every other
stack, selected by ``mpnn_type: "KEYEVL2"`` in ``keye_vl2.json``. Every layer
attends only the keys a learned indexer selects (16 a query here, 2,048 as
published) and trains the indexer on its own loss; softmax top-k experts with
an auxiliary balancing loss. The published widths are in
``benchmarks/configs/keye_vl2_a3b_ep8.json``.

    python examples/keye_vl2/keye_vl2.py [--num_docs 96] [--num_epoch 3]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

import hydragnn_tpu  # noqa: E402
from hydragnn_tpu.data.pipeline import split_dataset  # noqa: E402
from hydragnn_tpu.data.synthetic import packed_documents_dataset  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--num_docs", type=int, default=96)
    ap.add_argument("--num_epoch", type=int, default=None)
    args = ap.parse_args()
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "keye_vl2.json")) as f:
        config = json.load(f)
    if args.num_epoch is not None:
        config["NeuralNetwork"]["Training"]["num_epoch"] = args.num_epoch
    vocab = config["NeuralNetwork"]["Architecture"]["vocab_size"]
    docs = packed_documents_dataset(args.num_docs, median_tokens=40.0, sigma=0.7, min_tokens=8,
                                    max_tokens=160, vocab_size=vocab, seed=0)
    datasets = split_dataset(docs, 0.8, seed=0)
    _, _, hist, _, _, _ = hydragnn_tpu.run_training(config, datasets=datasets)
    print("train loss by epoch:", [round(float(x), 4) for x in hist["train"]])
    assert hist["train"][-1] < hist["train"][0], hist["train"]


if __name__ == "__main__":
    main()
