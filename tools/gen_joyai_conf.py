#!/usr/bin/env python3
"""Write the net conf of a published sequence model in this repo's
dialect, from the published config's own keys. Four families:

* JoyAI-LLM-Flash's (the DeepSeek-V3 family: latent attention, one
  leading dense layer, sigmoid-routed experts with a shared one, one
  multi-token-prediction module) — any file without ``model_type``
  ``laguna``;
* ``model_type`` ``laguna`` (poolside's Laguna family: grouped-query
  attention whose layers are full or windowed by ``layer_types``, with
  head counts by layer, per-head output gates and a rotary by layer
  type, plain or YaRN, over part of the head; leading dense layers by
  ``mlp_only_layers``; softmax top-k experts without drops with a shared
  one);
* ``model_type`` ``KeyeVL2`` (Kwai-Keye's Keye-VL-2.0 language model:
  grouped-query attention over the keys a learned indexer picks,
  ``sa_config``, as the kind ``dsa``; an RMS norm on each head's q and k;
  a rotary by ``rope_scaling.mrope_section``; all layers alike, softmax
  top-k experts without drops and without a shared one);
* ``model_type`` ``lfm2_moe`` (LiquidAI's LFM2 mixture-of-experts family:
  ``layer_types`` sets each layer's operator, ``conv`` the gated short
  convolution as the kind ``shortconv``, ``full_attention`` grouped-query
  attention with an RMS norm on each head's q and k and the plain rotary
  over the whole head; the first ``num_dense_layers`` take a SwiGLU
  ``ffn``, the rest sigmoid top-k experts with a selection bias and
  without a shared one).

    python tools/gen_joyai_conf.py benchmarks/configs/joyai_llm_flash.json
    python tools/gen_joyai_conf.py benchmarks/configs/laguna_s_2_1.json
    python tools/gen_joyai_conf.py benchmarks/configs/keye_vl_2_0_30b_a3b.json
    python tools/gen_joyai_conf.py benchmarks/configs/lfm2_8b_a1b.json

reads the keys of that JSON (the model's ``config.json`` names plus the
held experts' ``expert_first`` and published count, for the first family
``mtp_loss_weight`` and ``bias_update_rate``, for the third
``index_loss_coef``, for the fourth ``bias_update_rate``, and the conf's
training pairs under ``train``) and prints the conf.
``benchmarks/configs/joyai_llm_flash.conf``,
``benchmarks/configs/laguna_s_2_1.conf``,
``benchmarks/configs/keye_vl_2_0_30b_a3b.conf``,
``benchmarks/configs/lfm2_8b_a1b.conf`` and the toy confs under
``tests/benchmarks/data/*_toy/configs/`` are its output; nothing reads
this file at run time.
"""

import json
import sys

_HEADER = ["# written by tools/gen_joyai_conf.py from the keys of the",
           "# configuration file beside it; edit that, not this",
           "netconfig=start"]


def _tail(c: dict, metrics) -> list:
    """What follows the net: the input's shape, the label and the
    training pairs, then the train metrics."""
    return ["netconfig=end", "", f"input_shape = 1,1,{c['positions']}",
            f"label_vec[0,{c['positions']}) = label",
            *(f"{k} = {v}" for k, v in c["train"].items()), *metrics]


def conf(c: dict) -> str:
    if c.get("model_type") == "laguna":
        return conf_laguna(c)
    if c.get("model_type") == "KeyeVL2":
        return conf_keye(c)
    if c.get("model_type") == "lfm2_moe":
        return conf_lfm2(c)
    return conf_joyai(c)


def conf_lfm2(c: dict) -> str:
    if not c["norm_topk_prob"] or not c["use_expert_bias"]:
        raise ValueError("norm_topk_prob or use_expert_bias is false: the "
                         "sigmoid router has the renormalised, biased "
                         "choice only")
    if c["conv_bias"]:
        raise ValueError("the shortconv kind has no bias")
    kinds = c["layer_types"]
    if len(kinds) != c["num_hidden_layers"]:
        raise ValueError(f"{len(kinds)} layer_types for "
                         f"{c['num_hidden_layers']} layers")
    E, V, eps = c["hidden_size"], c["vocab_size"], c["norm_eps"]
    heads = c["num_attention_heads"]
    out = _HEADER + ["layer[0->e0] = embed:tok_embed",
                     f"  nhidden = {E}", f"  vocab_size = {V}"]

    def norm(src, dst, name):
        out.extend([f"layer[{src}->{dst}] = rmsnorm:{name}",
                    f"  eps = {eps}"])

    x = "e0"
    for i, kind in enumerate(kinds):
        p = f"b{i}"
        norm(x, f"{p}n1", f"{p}_ln1")
        if kind == "conv":
            out.extend([f"layer[{p}n1->{p}a] = shortconv:{p}_conv",
                        f"  conv_L_cache = {c['conv_L_cache']}"])
        elif kind == "full_attention":
            out.extend([
                f"layer[{p}n1->{p}a] = gqa:{p}_attn",
                f"  nhead = {heads}",
                f"  nkvhead = {c['num_key_value_heads']}",
                f"  head_dim = {E // heads}",
                "  qk_norm = 1",
                f"  eps = {eps}",
                f"  rope_theta = {c['rope_theta']}"])
        else:
            raise ValueError(f"layer_types {kind!r}")
        out.append(f"layer[{x},{p}a->{p}r1] = add:{p}_res1")
        norm(f"{p}r1", f"{p}n2", f"{p}_ln2")
        if i < c["num_dense_layers"]:
            out.extend([f"layer[{p}n2->{p}f] = ffn:{p}_ffn",
                        "  act = swiglu",
                        f"  nhidden = {c['intermediate_size']}"])
        else:
            out.extend([
                f"layer[{p}n2->{p}f] = moe:{p}_moe",
                "  router = sigmoid",
                f"  num_expert = {c['num_experts_published']}",
                f"  topk = {c['num_experts_per_tok']}",
                f"  nhidden = {c['moe_intermediate_size']}",
                "  shared_expert = 0",
                f"  routed_scaling_factor = {c['routed_scaling_factor']}",
                f"  expert_first = {c['expert_first']}",
                f"  expert_held = {c['num_experts']}",
                f"  bias_update_rate = {c['bias_update_rate']}"])
        out.append(f"layer[{p}r1,{p}f->{p}r2] = add:{p}_res2")
        x = f"{p}r2"
    norm(x, "hN", "final_norm")
    out.extend(["layer[hN->lg] = seqfc:lm_head", f"  nhidden = {V}",
                "  no_bias = 1",
                "layer[lg->lg] = lmloss:loss_main"])
    out.extend(_tail(c, ["metric[label,lg] = seq_error",
                         "metric[label,lg] = seq_logloss"]))
    return "\n".join(out) + "\n"


def conf_keye(c: dict) -> str:
    if not c["norm_topk_prob"]:
        raise ValueError("norm_topk_prob is false: the moe kind has the "
                         "renormalised gates only")
    if c["attention_bias"] or c["mlp_only_layers"] \
            or c["decoder_sparse_step"] != 1 or c["use_sliding_window"] \
            or c["tie_word_embeddings"]:
        raise ValueError("attention biases, dense layers among the expert "
                         "layers, a window and a tied head are not written")
    rope, sa = c["rope_scaling"], c["sa_config"]
    if rope["rope_type"] != "default" or sa["indexer_num_kv_heads"] != 1:
        raise ValueError("a scaled rotary and an indexer with several key "
                         "heads are not written")
    E, V, eps = c["hidden_size"], c["vocab_size"], c["rms_norm_eps"]
    out = _HEADER + ["layer[0->e0] = embed:tok_embed",
                     f"  nhidden = {E}", f"  vocab_size = {V}"]

    def norm(src, dst, name):
        out.extend([f"layer[{src}->{dst}] = rmsnorm:{name}",
                    f"  eps = {eps}"])

    x = "e0"
    for i in range(c["num_hidden_layers"]):
        p = f"b{i}"
        norm(x, f"{p}n1", f"{p}_ln1")
        out.extend([
            f"layer[{p}n1->{p}a] = dsa:{p}_attn",
            f"  nhead = {c['num_attention_heads']}",
            f"  nkvhead = {c['num_key_value_heads']}",
            f"  head_dim = {c['head_dim']}",
            "  qk_norm = 1",
            f"  eps = {eps}",
            f"  rope_theta = {c['rope_theta']}",
            "  mrope_section = "
            + ",".join(str(n) for n in rope["mrope_section"]),
            f"  index_heads = {sa['indexer_num_heads']}",
            f"  index_head_dim = {sa['indexer_head_dim']}",
            f"  index_topk = {sa['topk']}",
            f"  index_loss_coef = {c['index_loss_coef']}"])
        out.append(f"layer[{x},{p}a->{p}r1] = add:{p}_res1")
        norm(f"{p}r1", f"{p}n2", f"{p}_ln2")
        out.extend([
            f"layer[{p}n2->{p}f] = moe:{p}_moe",
            "  router = softmax_nodrop",
            f"  num_expert = {c['num_experts_published']}",
            f"  topk = {c['num_experts_per_tok']}",
            f"  nhidden = {c['moe_intermediate_size']}",
            "  shared_expert = 0",
            "  routed_scaling_factor = 1",
            f"  expert_first = {c['expert_first']}",
            f"  expert_held = {c['num_experts']}"])
        out.append(f"layer[{p}r1,{p}f->{p}r2] = add:{p}_res2")
        x = f"{p}r2"
    norm(x, "hN", "final_norm")
    out.extend(["layer[hN->lg] = seqfc:lm_head", f"  nhidden = {V}",
                "  no_bias = 1",
                "layer[lg->lg] = lmloss:loss_main"])
    out.extend(_tail(c, ["metric[label,lg] = seq_error",
                         "metric[label,lg] = seq_logloss"]))
    return "\n".join(out) + "\n"


def conf_laguna(c: dict) -> str:
    if not c["norm_topk_prob"]:
        raise ValueError("norm_topk_prob is false: the moe kind has the "
                         "renormalised gates only")
    if c["gating"] != "per-head":
        raise ValueError(f"gating {c['gating']!r}: the gqa kind has the "
                         "per-head gate only")
    if c["moe_router_logit_softcapping"] or c[
            "moe_apply_router_weight_on_input"] or c["attention_bias"]:
        raise ValueError("a capped router logit, the gate on the expert's "
                         "input and attention biases are not written")
    shared, rest = divmod(c["shared_expert_intermediate_size"],
                          c["moe_intermediate_size"])
    if rest:
        raise ValueError("the shared expert is not a whole number of "
                         "routed experts wide")
    E, V, eps = c["hidden_size"], c["vocab_size"], c["rms_norm_eps"]
    out = _HEADER + ["layer[0->e0] = embed:tok_embed",
                     f"  nhidden = {E}", f"  vocab_size = {V}"]

    def norm(src, dst, name):
        out.extend([f"layer[{src}->{dst}] = rmsnorm:{name}",
                    f"  eps = {eps}"])

    def attn(src, dst, name, kind, heads):
        window = {"full_attention": 0,
                  "sliding_attention": c["sliding_window"]}[kind]
        r = c["rope_parameters"][kind]
        rotary = int(round(c["head_dim"] * r["partial_rotary_factor"]))
        out.extend([
            f"layer[{src}->{dst}] = gqa:{name}",
            f"  nhead = {heads}",
            f"  nkvhead = {c['num_key_value_heads']}",
            f"  head_dim = {c['head_dim']}",
            f"  window = {window}",
            "  head_gate = 1",
            f"  rotary_dim = {rotary}",
            f"  rope_theta = {r['rope_theta']}",
            f"  rope_type = {r['rope_type']}"])
        if r["rope_type"] == "yarn":
            out.extend([
                f"  rope_factor = {r['factor']}",
                "  rope_original_max_position = "
                f"{r['original_max_position_embeddings']}",
                f"  rope_beta_fast = {r['beta_fast']}",
                f"  rope_beta_slow = {r['beta_slow']}",
                f"  rope_attention_factor = {r['attention_factor']}"])
        elif r["rope_type"] != "default":
            raise ValueError(f"rope_type {r['rope_type']!r}")

    x = "e0"
    for i in range(c["num_hidden_layers"]):
        p = f"b{i}"
        norm(x, f"{p}n1", f"{p}_ln1")
        attn(f"{p}n1", f"{p}a", f"{p}_attn", c["layer_types"][i],
             c["num_attention_heads_per_layer"][i])
        out.append(f"layer[{x},{p}a->{p}r1] = add:{p}_res1")
        norm(f"{p}r1", f"{p}n2", f"{p}_ln2")
        if i in c["mlp_only_layers"] or (i + 1) % c["decoder_sparse_step"]:
            out.extend([f"layer[{p}n2->{p}f] = ffn:{p}_ffn",
                        "  act = swiglu",
                        f"  nhidden = {c['intermediate_size']}"])
        else:
            out.extend([
                f"layer[{p}n2->{p}f] = moe:{p}_moe",
                "  router = softmax_nodrop",
                f"  num_expert = {c['num_experts_published']}",
                f"  topk = {c['num_experts_per_tok']}",
                f"  nhidden = {c['moe_intermediate_size']}",
                f"  shared_expert = {shared}",
                "  routed_scaling_factor = "
                f"{c['moe_routed_scaling_factor']}",
                f"  expert_first = {c['expert_first']}",
                f"  expert_held = {c['num_experts']}"])
        out.append(f"layer[{p}r1,{p}f->{p}r2] = add:{p}_res2")
        x = f"{p}r2"
    norm(x, "hN", "final_norm")
    out.extend(["layer[hN->lg] = seqfc:lm_head", f"  nhidden = {V}",
                "  no_bias = 1",
                "layer[lg->lg] = lmloss:loss_main"])
    out.extend(_tail(c, ["metric[label,lg] = seq_error",
                         "metric[label,lg] = seq_logloss"]))
    return "\n".join(out) + "\n"


def conf_joyai(c: dict) -> str:
    for key in ("norm_topk_prob", "rope_interleave"):
        if not c[key]:
            raise ValueError(f"{key} is false: the moe and mla kinds have "
                             "the published form only")
    E, V = c["hidden_size"], c["vocab_size"]
    out = _HEADER + ["layer[0->e0] = embed:tok_embed",
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
    out.extend(_tail(c, ["metric[label,lg] = seq_error",
                         "metric[label,lg] = seq_logloss",
                         "metric[label,mlg] = seq_logloss"]))
    return "\n".join(out) + "\n"


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        sys.stdout.write(conf(json.load(f)))
