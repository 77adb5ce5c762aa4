"""Sizes worked out from a configuration file, with no help from the program.

``flat_params`` is the length of the flat gradient the aggregation rounds
move: every parameter of the model, the vocabulary padded as the
configuration's ``assumed.padded_vocab`` says.
"""

from __future__ import annotations


def mamba2_dims(c: dict) -> dict:
    d = int(c["d_model"])
    di = int(c["expand"]) * d
    n = int(c["d_state"])
    p = int(c["headdim"])
    h = di // p
    return {"d": d, "di": di, "n": n, "p": p, "h": h,
            "layers": int(c["n_layer"]), "conv": int(c["d_conv"]),
            "vocab": int(c["assumed"]["padded_vocab"]),
            "chunk": int(c["chunk_size"]),
            "in_proj": 2 * di + 2 * n + h, "conv_ch": di + 2 * n}


def mamba2_params(c: dict) -> int:
    m = mamba2_dims(c)
    per_layer = (m["d"] * m["in_proj"]          # in_proj
                 + m["conv"] * m["conv_ch"]     # depthwise conv
                 + 3 * m["h"]                   # dt_bias, A_log, D
                 + m["di"]                      # gated RMSNorm scale
                 + m["di"] * m["d"]             # out_proj
                 + m["d"])                      # pre-norm scale
    # tied embedding and head, and the final norm
    return m["layers"] * per_layer + m["vocab"] * m["d"] + m["d"]


def flat_params(c: dict) -> int:
    if c["family"] == "mamba2":
        return mamba2_params(c)
    if c["family"] == "logreg":
        return int(c["input_dim"]) * int(c["num_classes"]) + int(
            c["num_classes"])
    raise ValueError(f"unknown family {c['family']!r}")

