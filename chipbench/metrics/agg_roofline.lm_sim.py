"""The aggregation kernel's share of its HBM roofline in the federated-LoRA
cells.

Needed bytes per round: each realized upload's adapter delta read once, the
global adapters read and written once — (uploads × D + 2·D) × 4 bytes, D
the adapters' parameter count computed from the configuration — whatever
rows, leaves or padding the kernel actually touches.  Uploads over the
window come from the program's counter ``engine.upload_bytes`` (realized
uploads × D × 4).  Least time = bytes / HBM peak; share = least time over
the summed device time of the kernel's events in the trace.
"""
#: the Pallas call's events: the custom call named after its jitted wrapper
#: in ``kernels/fl_aggregate.py`` (``%fl_aggregate.N = ... tpu_custom_call``)
KERNEL = ("%fl_aggregate.", 'custom_call_target="tpu_custom_call"')


def adapter_params(cfg) -> int:
    """D = Σ r·(n_in + n_out) over the adapted matrices of every layer."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // H
    KV, di, ff = (cfg["num_key_value_heads"], cfg["mamba_expand"] * d,
                  cfg["intermediate_size"])
    r, targets = cfg["lora"]["r"], set(cfg["lora"]["targets"])
    mats = {"attn": {"wq": (d, H * hd), "wk": (d, KV * hd),
                     "wv": (d, KV * hd), "wo": (H * hd, d)},
            "mamba": {"in_proj": (d, 2 * di), "out_proj": (di, d)}}
    ffn = {"w1": (d, ff), "w3": (d, ff), "w2": (ff, d)}
    total = 0
    for i in range(cfg["num_hidden_layers"]):
        attn = i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
        for k, (a, b) in {**mats["attn" if attn else "mamba"],
                          **ffn}.items():
            if k in targets:
                total += r * (a + b)
    return total


def needed_bytes(uploads: float, rounds: int, d: int) -> float:
    """Bytes the window's aggregations must move for ``uploads`` received
    deltas over ``rounds`` rounds."""
    return (float(uploads) * d + 2.0 * d * rounds) * 4.0


def read(ctx):
    red, c = ctx["trace"], ctx["counts"]
    if red is None or c.get("engine.upload_bytes") is None:
        return None
    secs, n = ctx["trace_ops"](*KERNEL)
    if n == 0 or secs <= 0:
        return None
    d = adapter_params(ctx["config"])
    uploads = c["engine.upload_bytes"] / (4.0 * d)
    need = needed_bytes(uploads, c["rounds"], d)
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / secs
