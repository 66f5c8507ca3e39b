"""The whole federated-LoRA round's share of the chip's bf16 peak.

Needed FLOPs per trained token: 4·P_mm for the frozen base (the forward
pass and the backward pass to the activations; no weight gradient),
6·P_lora for the adapters, and attention's score and value products (three
times the forward's, causal pairs only).  Per evaluated token: 2·P_mm,
2·P_lora and the forward's attention products.  P_mm counts the base's
matrix weights a token meets (every projection, the tied unembedding);
P_lora the adapters' A and B.  Recomputed (checkpointed) work, the Mamba
scan's elementwise work and padding do not count.  Trained tokens come
from the program's counter ``engine.train_tokens``, evaluated tokens from
the evaluations the calls returned; over window × the bf16 peak (the
float32 activations meet bfloat16 weights in one bf16 pass at default
precision).
"""


def _shape(cfg):
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // H
    return d, H, hd, cfg["num_key_value_heads"], cfg["mamba_expand"] * d


def _attn_layers(cfg):
    return [i for i in range(cfg["num_hidden_layers"])
            if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]]


def p_mm(cfg) -> int:
    """Matrix weights a token meets in the frozen base."""
    d, H, hd, KV, di = _shape(cfg)
    R, N = cfg["mamba_dt_rank"], cfg["mamba_d_state"]
    n_attn = len(_attn_layers(cfg))
    n_mamba = cfg["num_hidden_layers"] - n_attn
    ffn = 3 * d * cfg["intermediate_size"]
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    mamba = d * 2 * di + di * (R + 2 * N) + R * di + di * d
    return (n_attn * (attn + ffn) + n_mamba * (mamba + ffn)
            + d * cfg["vocab_size"])


def p_lora(cfg) -> int:
    """Adapter weights a token meets: r·(n_in + n_out) per adapted matrix."""
    d, H, hd, KV, di = _shape(cfg)
    r, targets = cfg["lora"]["r"], set(cfg["lora"]["targets"])
    ff = cfg["intermediate_size"]
    attn = {"wq": (d, H * hd), "wk": (d, KV * hd), "wv": (d, KV * hd),
            "wo": (H * hd, d)}
    mamba = {"in_proj": (d, 2 * di), "out_proj": (di, d)}
    ffn = {"w1": (d, ff), "w3": (d, ff), "w2": (ff, d)}
    n_attn = len(_attn_layers(cfg))
    per = lambda mats: sum(r * (i + o) for k, (i, o) in mats.items()  # noqa
                           if k in targets)
    return (n_attn * (per(attn) + per(ffn))
            + (cfg["num_hidden_layers"] - n_attn) * (per(mamba) + per(ffn)))


def attn_fwd_flops_per_token(cfg, seq: int) -> float:
    """Forward QKᵀ and PV products per token of a causal ``seq``-token
    sequence: 4·H·hd per attended (query, key) pair, S(S+1)/2 pairs."""
    d, H, hd, KV, di = _shape(cfg)
    pairs = seq * (seq + 1) / 2.0
    return len(_attn_layers(cfg)) * 4.0 * H * hd * pairs / seq


def train_flops(cfg, tokens: float, seq: int) -> float:
    return tokens * (4.0 * p_mm(cfg) + 6.0 * p_lora(cfg)
                     + 3.0 * attn_fwd_flops_per_token(cfg, seq))


def eval_flops(cfg, tokens: float, seq: int) -> float:
    return tokens * (2.0 * p_mm(cfg) + 2.0 * p_lora(cfg)
                     + attn_fwd_flops_per_token(cfg, seq))


def read(ctx):
    c, cfg = ctx["counts"], ctx["config"]
    tokens = c.get("engine.train_tokens")
    if not tokens or ctx["window_s"] <= 0:
        return None
    seq = cfg["data"]["tokens_per_sequence"] - 1
    flops = (train_flops(cfg, tokens, seq)
             + eval_flops(cfg, c["eval_tokens"], seq))
    return 100.0 * flops / (ctx["window_s"]
                            * ctx["peaks"]["bf16_flops_per_s"])
