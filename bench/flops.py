"""Model FLOPs per token, from a configuration file's sizes.

Counts the multiply-adds the architecture requires, at 2 FLOPs each: every
matrix product with a parameter matrix (the tied output projection included,
the embedding lookup not), and the products between activations (attention's
scores and weighted values, over the causal half of the context; the SSD's
intra-chunk and state products).  Training is three times the forward (the
backward pass is twice it); recomputation under remat is not counted.

The forward count of an architecture lives with its reference model, as
``flops_per_token(sizes, seq)`` in ``bench/reference/<reference>.py``, found
by the name the configuration file gives under ``"reference"``.
"""
from __future__ import annotations

from bench import reference


def per_token(conf: dict, seq: int, training: bool) -> float:
    fwd = reference.model(conf["reference"]).flops_per_token(conf["sizes"],
                                                              seq)
    return 3.0 * fwd if training else fwd
