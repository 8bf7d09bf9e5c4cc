"""Operations and bytes the work needs, from the configuration's shapes.

Model FLOPs per trained token (forward and backward, recomputation not
counted): 6 x the parameters that multiply a token's activations (every
projection of every layer and the output head once, the head tied to the
embedding), plus 12 x layers x sequence x the attention width for the
score and value products (the causal mask not subtracted).

Stats bytes per step: every probed tensor read once at its own dtype,
whatever implements the read.
"""
from __future__ import annotations

from weights import head_dim, padded_vocab

BF16, F32 = 2, 4


def matmul_params(model: dict) -> int:
    D, H, KH, hd = (model["d_model"], model["num_heads"],
                    model["num_kv_heads"], head_dim(model))
    attn = D * H * hd + 2 * D * KH * hd + H * hd * D
    mlp = 3 * D * model["d_ff"]
    return model["num_layers"] * (attn + mlp) + D * model["vocab_size"]


def flops_per_token(model: dict, seq_len: int) -> float:
    attn_width = model["num_heads"] * head_dim(model)
    return 6.0 * matmul_params(model) \
        + 12.0 * model["num_layers"] * seq_len * attn_width


def site_bytes(model: dict, batch: int, seq_len: int) -> dict:
    """Bytes of the tensor at each probe site: (site, kind) -> bytes. The
    residual stream and the sublayer outputs are bf16; the logits are f32
    over the padded vocabulary the step computes; loss and gradient norm
    are one f32 each."""
    act = batch * seq_len * model["d_model"] * BF16
    return {("block", 0): act, ("block", 1): act, ("attn.out", 2): act,
            ("ffn.out", 2): act, ("embed.out", 2): act,
            ("logits", 2): batch * seq_len * padded_vocab(model) * F32,
            ("loss", 2): F32, ("grad.norm", 2): F32}


PER_LAYER = {("block", 0), ("block", 1), ("attn.out", 2), ("ffn.out", 2)}
KIND = {"uprobe": 0, "uretprobe": 1, "probe": 2}


def probed_sites(traffic: dict) -> set:
    """(site, kind) pairs that some program of the traffic attaches to."""
    return {(t.split(":")[1], KIND[t.split(":")[0]])
            for p in traffic.get("programs", []) for t in p["targets"]}


def stats_bytes_per_step(model: dict, traffic: dict) -> int:
    """Bytes the stats of one step must read: each probed site once per
    event (per-layer sites once per layer)."""
    sizes = site_bytes(model, traffic["batch"], traffic["seq_len"])
    total = 0
    for site in probed_sites(traffic):
        n = model["num_layers"] if site in PER_LAYER else 1
        total += n * sizes[site]
    return total
