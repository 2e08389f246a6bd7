"""Parameter count and FLOPs of one denoiser evaluation, from shapes.

A row-eval is one forward pass of the DiT over one latent row (the CFG
pair of one image at one step is two row-evals, as the scheduler's NFE
counts them).  FLOPs count every matrix product at 2 per multiply-add:
the patch, timestep, conditioning, adaLN and output projections, the
q/k/v/o projections of self- and cross-attention, the two attention
products of each (scores and the weighted sum of values), and the MLP.
Norms, softmax, activations, RoPE and the solver update are elementwise
and left out (under 1% of the total at both published widths).

Computed from ``bench/configs/<config>.json``, never from the program's
own ``ModelConfig.n_params()`` (which undercounts this model).
"""
from __future__ import annotations


def _dims(spec: dict):
    d, L = spec["d_model"], spec["n_layers"]
    hh = spec["n_heads"] * spec["head_dim"]
    p_in = spec["patch"] ** 2 * spec["latent_channels"]
    S = (spec["latent_size"] // spec["patch"]) ** 2
    return d, L, hh, p_in, S


def param_count(spec: dict) -> int:
    """Parameters of the DiT tree (bench/weights.py ``dit_tree``)."""
    d, L, hh, p_in, S = _dims(spec)
    attn = 4 * d * hh + (2 * spec["head_dim"] if spec["qk_norm"] else 0)
    block = (d * 6 * d + 6 * d          # adaLN
             + 2 * attn                 # self- and cross-attention
             + d                        # lnx
             + 2 * d * spec["d_ff"])    # MLP
    return (p_in * d + S * d            # patch_in, pos
            + spec["timestep_dim"] * d + d * d      # t_w1, t_w2
            + spec["cond_dim"] * d                  # cond_proj
            + L * block
            + d * 2 * d + 2 * d                     # final adaLN
            + d * p_in)                             # out


def row_eval_flops(spec: dict) -> float:
    """FLOPs of one denoiser evaluation of one latent row."""
    d, L, hh, p_in, S = _dims(spec)
    Lc, ff = spec["cond_len"], spec["d_ff"]
    per_row = 2 * (spec["timestep_dim"] * d + d * d)       # timestep MLP
    per_row += 2 * Lc * spec["cond_dim"] * d               # cond_proj
    per_row += 2 * S * p_in * d * 2                        # patch in, out
    per_row += 2 * d * 2 * d                               # final adaLN
    block = 2 * d * 6 * d                                  # adaLN
    block += 2 * S * d * hh * 4                            # self q,k,v,o
    block += 2 * S * S * hh * 2                            # self QK, PV
    block += 2 * S * d * hh * 2 + 2 * Lc * d * hh * 2      # cross q,o; k,v
    block += 2 * S * Lc * hh * 2                           # cross QK, PV
    block += 2 * S * d * ff * 2                            # MLP
    return float(per_row + L * block)
