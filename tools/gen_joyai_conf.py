#!/usr/bin/env python3
"""Write the net conf of a JoyAI-LLM-Flash-shaped model (the DeepSeek-V3
family: latent attention, one leading dense layer, sigmoid-routed
experts with a shared one, one multi-token-prediction module) in this
repo's dialect, from the published config's own keys.

    python tools/gen_joyai_conf.py benchmarks/configs/joyai_llm_flash.json

reads the keys of that JSON (the model's ``config.json`` names plus
``expert_first`` / ``expert_held``, ``mtp_loss_weight``,
``bias_update_rate`` and the conf's training pairs under ``train``) and
prints the conf. ``benchmarks/configs/joyai_llm_flash.conf`` and
``tests/benchmarks/data/joyai_toy/configs/joyai_toy.conf`` are its
output; nothing reads this file at run time.
"""

import json
import sys


def conf(c: dict) -> str:
    for key in ("norm_topk_prob", "rope_interleave"):
        if not c[key]:
            raise ValueError(f"{key} is false: the moe and mla kinds have "
                             "the published form only")
    E, V = c["hidden_size"], c["vocab_size"]
    out = ["# written by tools/gen_joyai_conf.py from the keys of the",
           "# configuration file beside it; edit that, not this",
           "netconfig=start",
           "layer[0->e0] = embed:tok_embed",
           f"  nhidden = {E}", f"  vocab_size = {V}"]

    def attn(src, dst, name):
        out.extend([
            f"layer[{src}->{dst}] = mla:{name}",
            f"  nhead = {c['num_attention_heads']}",
            f"  q_lora_rank = {c['q_lora_rank']}",
            f"  kv_lora_rank = {c['kv_lora_rank']}",  # graftlint: disable=config-namespace (the model's own config.json key)
            f"  qk_nope_head_dim = {c['qk_nope_head_dim']}",
            f"  qk_rope_head_dim = {c['qk_rope_head_dim']}",
            f"  v_head_dim = {c['v_head_dim']}",
            f"  rope_theta = {c['rope_theta']}",
            f"  eps = {c['rms_norm_eps']}"])

    def norm(src, dst, name):
        out.extend([f"layer[{src}->{dst}] = rmsnorm:{name}",
                    f"  eps = {c['rms_norm_eps']}"])

    def experts(src, dst, name):
        out.extend([
            f"layer[{src}->{dst}] = moe:{name}",
            "  router = sigmoid",
            f"  num_expert = {c['n_routed_experts_published']}",
            f"  topk = {c['num_experts_per_tok']}",
            f"  nhidden = {c['moe_intermediate_size']}",
            f"  shared_expert = {c['n_shared_experts']}",
            f"  routed_scaling_factor = {c['routed_scaling_factor']}",
            f"  expert_first = {c['expert_first']}",
            f"  expert_held = {c['n_routed_experts']}",
            f"  bias_update_rate = {c['bias_update_rate']}"])

    def block(x, p, dense):
        """One pre-norm block on node ``x``; returns its output node."""
        norm(x, f"{p}n1", f"{p}_ln1")
        attn(f"{p}n1", f"{p}a", f"{p}_attn")
        out.append(f"layer[{x},{p}a->{p}r1] = add:{p}_res1")
        norm(f"{p}r1", f"{p}n2", f"{p}_ln2")
        if dense:
            out.extend([f"layer[{p}n2->{p}f] = ffn:{p}_ffn",
                        "  act = swiglu",
                        f"  nhidden = {c['intermediate_size']}"])
        else:
            experts(f"{p}n2", f"{p}f", f"{p}_moe")
        out.append(f"layer[{p}r1,{p}f->{p}r2] = add:{p}_res2")
        return f"{p}r2"

    x = "e0"
    for i in range(c["num_hidden_layers"]):
        x = block(x, f"b{i}", i < c["first_k_dense_replace"])
    norm(x, "hN", "final_norm")
    out.extend(["layer[hN->lg] = seqfc:lm_head", f"  nhidden = {V}",
                "  no_bias = 1",
                "layer[lg->lg] = lmloss:loss_main"])
    for d in range(c["num_nextn_predict_layers"]):
        if d:
            raise ValueError("one multi-token-prediction module is all "
                             "this writer chains")
        # the module: [RMS(h) ; RMS(Emb(next token))] W_eh, one expert
        # block, a norm, the main model's own embedding and head
        out.extend(["layer[0->nx] = label_ids:mtp_next_ids",
                    "layer[nx->ne] = share[tok_embed]:mtp_embed"])
        norm("hN", "mh", "mtp_hnorm")
        norm("ne", "me", "mtp_enorm")
        out.extend(["layer[mh,me->mc] = ch_concat:mtp_cat",
                    "layer[mc->m0] = seqfc:mtp_eh", f"  nhidden = {E}",
                    "  no_bias = 1"])
        x = block("m0", "mtp", False)
        norm(x, "mN", "mtp_final_norm")
        out.extend(["layer[mN->mlg] = share[lm_head]:mtp_head",
                    "layer[mlg->mlg] = lmloss:loss_mtp", "  shift = 1",
                    f"  grad_scale = {c['mtp_loss_weight']}"])
    out.append("netconfig=end")
    out.append("")
    out.append(f"input_shape = 1,1,{c['positions']}")
    out.append(f"label_vec[0,{c['positions']}) = label")
    out.extend(f"{k} = {v}" for k, v in c["train"].items())
    out.append("metric[label,lg] = seq_error")
    out.append("metric[label,lg] = seq_logloss")
    out.append("metric[label,mlg] = seq_logloss")
    return "\n".join(out) + "\n"


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        sys.stdout.write(conf(json.load(f)))
