"""Operations the LM cells' training step requires, from the shapes of the
configuration's published keys (``configs/deepseek_v2_lite.json``).

What the mathematics needs, whatever implements it: 2 FLOPs per
multiply-add; the forward pass and the backward pass's activation
gradients (the trunk is frozen, so no weight gradients of it; the
adapters' weight gradients are counted); causal attention counts the
scores at and below the diagonal only; recomputation (layer remat, the
loss's chunks) is not work.
"""
from __future__ import annotations


def dims(cfg: dict) -> dict:
    h = cfg["num_attention_heads"]
    return dict(
        d=cfg["hidden_size"], h=h, dq=cfg["qk_nope_head_dim"]
        + cfg["qk_rope_head_dim"], rope=cfg["qk_rope_head_dim"],
        v=cfg["v_head_dim"], c=cfg["kv_lora_rank"],
        k=cfg["num_experts_per_tok"], e=cfg["n_routed_experts"],
        f=cfg["moe_intermediate_size"],
        f_shared=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        layers=cfg["n_layers"], dense=cfg["first_k_dense_replace"])


def mla_macs_per_token(cfg: dict, seq_len: int) -> float:
    """Projections plus causal scores and values (mean over positions:
    (L + 1) / 2 keys a query)."""
    x = dims(cfg)
    proj = (x["d"] * x["h"] * x["dq"] + x["d"] * (x["c"] + x["rope"])
            + x["c"] * x["h"] * (x["dq"] - x["rope"] + x["v"])
            + x["h"] * x["v"] * x["d"])
    keys = (seq_len + 1) / 2
    return proj + keys * x["h"] * (x["dq"] + x["v"])


def expert_pair_flops(cfg: dict) -> int:
    """Per routed (token, expert) pair: forward and activation backward,
    each the SwiGLU's 3 matmuls of d x f: 2 x (2 x 3 x d x f)."""
    x = dims(cfg)
    return 2 * (2 * 3 * x["d"] * x["f"])


def moe_macs_per_token(cfg: dict) -> int:
    """k routed experts, the shared experts and the router."""
    x = dims(cfg)
    return 3 * x["d"] * (x["k"] * x["f"] + x["f_shared"]) + x["d"] * x["e"]


def forward_macs_per_token(cfg: dict, seq_len: int, rank: int) -> float:
    x = dims(cfg)
    n_moe = x["layers"] - x["dense"]
    lora = rank * (2 * x["d"] + x["h"] * x["dq"] + x["c"] + x["rope"]
                   + x["c"] + x["h"] * (x["dq"] - x["rope"] + x["v"])
                   + x["h"] * x["v"] + x["d"])
    return (x["layers"] * (mla_macs_per_token(cfg, seq_len) + lora)
            + x["dense"] * 3 * x["d"] * x["d_ff"]
            + n_moe * moe_macs_per_token(cfg) + x["d"] * x["vocab"])


def train_flops_per_token(cfg: dict, seq_len: int, rank: int) -> float:
    """Forward, activation backward (the same again, plus attention's
    second product per score: dQ and dK from dS, dP and dV from dO) and the
    adapters' weight gradients: 2 FLOPs a multiply-add."""
    x = dims(cfg)
    fwd = forward_macs_per_token(cfg, seq_len, rank)
    attn_extra = x["layers"] * (seq_len + 1) / 2 * x["h"] * (x["dq"] + x["v"])
    lora_wgrad = x["layers"] * rank * (
        2 * x["d"] + x["h"] * x["dq"] + x["c"] + x["rope"] + x["c"]
        + x["h"] * (x["dq"] - x["rope"] + x["v"]) + x["h"] * x["v"]
        + x["d"])
    return 2.0 * (2 * fwd + attn_extra + lora_wgrad)


def pairs_per_round(cfg: dict) -> int:
    """Routed (token, expert) pairs of one round, over every MoE layer."""
    x = dims(cfg)
    data = cfg["data"]
    tokens = (cfg["n_agents"] * data["local_updates"] * data["batch_size"]
              * data["dataset_params"]["seq_len"])
    return tokens * x["k"] * (x["layers"] - x["dense"])
