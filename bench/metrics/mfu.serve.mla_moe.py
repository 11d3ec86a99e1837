"""Active FLOPs of the traced part of the window over (its length x the
chip's bf16 peak), in percent, for a model with latent attention and
routed experts: every prompt prefilled there (each token's top-k pairs)
and every token decoded there (the routed pairs from the engine's counters
over the traced batch), each also through the weights outside the routed
experts, attention at its live length and the head (the configuration's
work counts). Padding, free slots and recomputation do not count."""


def read(rec):
    t = rec.get("trace")
    e = rec.get("traced_engine", {})
    if not t or t["window_s"] <= 0 or "work" not in rec or "moe_pairs" not in e:
        return None
    w, cfg, pk = rec["work"], rec["cfg"], rec["peaks"]
    work = sum(w.prefill_flops(cfg, s) for s in rec["traced_prefills"])
    decoded = [p for step in rec["traced_decodes"] for p in step]
    work += sum(w.token_flops(cfg) + w.decode_attention_flops(cfg, p + 1)
                for p in decoded)
    work += e["moe_pairs"] * w.pair_flops(cfg)
    if not work:
        return None
    return 100.0 * work / (t["window_s"] * rec["chips"] * pk.bf16_flops)
