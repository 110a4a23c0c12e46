"""GPT-2 causal LM pre-training: the program through paddle_tpu's normal
path, the seeded batch generator (a copy of `models/gpt.synthetic_batch`),
the required-FLOPs count, the parameter list and the plain reference.

Reference departures from Radford et al. 2019 / openai-community/gpt2, all
following what the program computes: erf GELU (published `gelu_new`, the
tanh form); layer-norm epsilon 1e-5 as published; no attention dropout in
the program at all; Adam in the epsilon-hat form, no weight decay or
schedule. The reference rematerialises each block (`jax.checkpoint`) only so
that T=4096 in float32 fits beside nothing else; that changes no value.
"""
import numpy as np

from benchmark import flops
from benchmark import reference as ref

LN_EPS = 1e-5


def _model_config(config, traffic):
    from paddle_tpu.models import gpt
    return gpt.GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["n_embd"],
        num_layers=config["n_layer"], num_heads=config["n_head"],
        ff_size=config["n_inner"], max_position=config["n_positions"],
        dropout=config["resid_pdrop"],
        initializer_range=config["initializer_range"],
        dtype=config["precision"], attn_impl="auto", recompute=True)


def build(config, traffic, optimizer_fn):
    from paddle_tpu.models import gpt
    main, startup, _feeds, fetch = gpt.gpt_pretrain_program(
        _model_config(config, traffic), batch_rows(traffic),
        traffic["seq_len"], optimizer_fn=optimizer_fn)
    return main, startup, fetch["loss"]


def batch_rows(traffic):
    return traffic["global_batch"]


def tokens_per_step(traffic):
    return batch_rows(traffic) * traffic["seq_len"]


def param_specs(config, traffic):
    """As openai/gpt-2 initialises them: normal of `initializer_range`
    (truncated here at two sigma, as the program's initialiser does) for
    matrices and tables, ones and zeros for layer norms and biases. Block
    matrices and biases are held in `precision`, the rest in float32."""
    h, ff = config["n_embd"], config["n_inner"]
    enc = config["precision"]
    specs = {
        "gpt_word_embedding": ((config["vocab_size"], h), "float32",
                               "normal"),
        "gpt_pos_embedding": ((config["n_positions"], h), "float32",
                              "normal"),
    }
    for i in range(config["n_layer"]):
        p = "gpt_layer_%d_" % i
        for ln in ("ln1", "ln2"):
            specs[p + ln + "_s"] = ((h,), "float32", "ones")
            specs[p + ln + "_b"] = ((h,), "float32", "zeros")
        for fc, shape in (("qkv", (h, 3 * h)), ("proj", (h, h)),
                          ("ffn0", (h, ff)), ("ffn1", (ff, h))):
            specs[p + fc + ".w_0"] = (shape, enc, "normal")
            specs[p + fc + ".b_0"] = ((shape[1],), enc, "zeros")
    specs["gpt_lnf_s"] = ((h,), "float32", "ones")
    specs["gpt_lnf_b"] = ((h,), "float32", "zeros")
    return specs


def make_batch(config, traffic, rng):
    """Random-but-valid LM batch: labels are the tokens shifted left, every
    position predicted."""
    n, t = batch_rows(traffic), traffic["seq_len"]
    toks = rng.integers(0, config["vocab_size"], (n, t + 1), dtype=np.int64)
    pos = np.tile(np.arange(t, dtype=np.int64).reshape(1, t, 1), (n, 1, 1))
    return {"token_ids": np.ascontiguousarray(toks[:, :-1, None]),
            "pos_ids": pos,
            "labels": np.ascontiguousarray(toks[:, 1:, None]),
            "loss_mask": np.ones((n, t, 1), np.float32)}


def train_flops(config, traffic):
    return flops.gpt_train_flops(
        config["n_embd"], config["n_layer"], config["n_inner"],
        config["vocab_size"], batch_rows(traffic), traffic["seq_len"])


def attention_calls(config, traffic):
    """The Pallas attention calls of one step, as (kind, batch, heads, seq,
    head_dim, causal, count): the program recomputes each block, so the
    forward kernel runs twice a layer."""
    t = traffic["seq_len"]
    if t * t <= 256 * 256:
        return []
    shape = (traffic["batch_per_chip"], config["n_head"], t,
             config["n_embd"] // config["n_head"], True)
    return [("forward",) + shape + (2 * config["n_layer"],),
            ("backward",) + shape + (config["n_layer"],)]


# ---- the plain reference -------------------------------------------------

def block_of(batch, lo, hi):
    return {"tok": batch["token_ids"][lo:hi, :, 0].astype(np.int32),
            "pos": batch["pos_ids"][lo:hi, :, 0].astype(np.int32),
            "lbl": batch["labels"][lo:hi, :, 0].astype(np.int32),
            "mask": batch["loss_mask"][lo:hi, :, 0]}


def reference_loss(p, blk, config, traffic, mm):
    """This block's contribution to the batch's loss: sum of the masked
    per-token cross-entropies over (the batch's count of predicted
    positions + 1e-8)."""
    import jax
    import jax.numpy as jnp
    h, heads = config["n_embd"], config["n_head"]
    dh = h // heads
    n, t = blk["tok"].shape
    predicted = batch_rows(traffic) * t     # loss_mask is all ones
    causal = jnp.tril(jnp.ones((t, t), bool))

    def split(y):
        return y.reshape(n, t, heads, dh).transpose(0, 2, 1, 3)

    def layer(x, w):
        y = ref.layer_norm(x, w["ln1_s"], w["ln1_b"], LN_EPS)
        qkv = mm(y, w["qkv.w_0"]) + w["qkv.b_0"]
        q, k, v = (split(z) for z in jnp.split(qkv, 3, axis=-1))
        scores = mm(q, k.transpose(0, 1, 3, 2)) * dh ** -0.5
        scores = jnp.where(causal, scores, -jnp.inf)
        ctx = mm(jax.nn.softmax(scores, axis=-1), v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(n, t, h)
        x = x + mm(ctx, w["proj.w_0"]) + w["proj.b_0"]
        y = ref.layer_norm(x, w["ln2_s"], w["ln2_b"], LN_EPS)
        y = ref.gelu(mm(y, w["ffn0.w_0"]) + w["ffn0.b_0"])
        return x + mm(y, w["ffn1.w_0"]) + w["ffn1.b_0"]

    x = p["gpt_word_embedding"][blk["tok"]] + p["gpt_pos_embedding"][
        blk["pos"]]
    for i in range(config["n_layer"]):
        prefix = "gpt_layer_%d_" % i
        w = {k[len(prefix):]: v for k, v in p.items()
             if k.startswith(prefix)}
        x = jax.checkpoint(layer)(x, w)
    x = ref.layer_norm(x, p["gpt_lnf_s"], p["gpt_lnf_b"], LN_EPS)
    logits = mm(x.reshape(n * t, h), p["gpt_word_embedding"].T)
    ce = ref.cross_entropy(logits, blk["lbl"].reshape(-1))
    return jnp.sum(ce * blk["mask"].reshape(-1)) / (predicted + 1e-8)
