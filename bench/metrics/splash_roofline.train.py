"""splash_roofline.train: the fused attention kernel's calls in the
traced window, as a share of their roofline (``bench/roofline.py``).

Each ``splash_mha_*`` operation is one kernel call over the whole batch
(the program ``vmap``s the kernel over it, which lowers to one
``pallas_call``); a train step holds four a layer: the forward, its
rematerialisation, ``dq`` and ``dkv``.  With B, S the configuration's
``train`` batch and length, H query and Hkv key-value heads of width D,
W = B H D S (S + 1) / 2 is one product over the causal pairs, the
diagonal included, and a call needs

    fwd   4W FLOPs (q.k, p.v)                   bytes q, k, v, o
    dq    6W (q.k again, dO.v, dS.k)            bytes q, k, v, dO, dq
    dkv   8W (q.k again, dO.v, p^T.dO, dS^T.q)  bytes q, k, v, dO, dk, dv

at 2 bytes an element, B S H D elements for q, o, dO, dq and B S Hkv D for
k, v, dk, dv: a lower bound of the work, compulsory traffic only.
"""
import re

from bench import roofline

PREFIX = "splash_mha_"
KIND = re.compile(PREFIX + r"(fwd|dq|dkv)(?=[_.]|$)")
FLOPS_W = {"fwd": 4, "dq": 6, "dkv": 8}


def counter(conf: dict):
    """count(op_name) -> (flops, bytes) of one call at the configuration's
    training shapes; None for a ``splash_mha_`` call of another kind."""
    s, t = conf["sizes"], conf["train"]
    B, S = t["batch"], t["seq_len"]
    H, Hkv, D = (s["num_attention_heads"], s["num_key_value_heads"],
                 s["head_dim"])
    w = B * H * D * S * (S + 1) / 2
    q, kv = B * S * H * D * 2, B * S * Hkv * D * 2
    nbytes = {"fwd": 2 * q + 2 * kv, "dq": 3 * q + 2 * kv,
              "dkv": 2 * q + 4 * kv}

    def count(op_name: str):
        m = KIND.match(op_name)
        if m is None:
            return None
        return FLOPS_W[m.group(1)] * w, nbytes[m.group(1)]
    return count


def read(ctx):
    if ctx.trace is None:
        return None
    return roofline.share(ctx.trace, PREFIX, counter(ctx.cell.config),
                          ctx.peaks)
