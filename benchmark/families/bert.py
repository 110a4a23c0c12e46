"""BERT MLM+NSP pre-training: the program through paddle_tpu's normal path,
the seeded batch generator (a copy of `models/bert.synthetic_batch`, with
the seed's generator passed in), the required-FLOPs count, the parameter
list the seeded weights are made from, and the plain reference.

Reference departures from Devlin et al. 2018 / google-research/bert, all
following what the program computes: layer-norm epsilon 1e-5 (published
1e-12); Adam in the epsilon-hat form with no weight decay, warm-up or decay
of the learning rate; the MLM loss is a mean over all prediction slots
(no padding slots in this traffic).
"""
import numpy as np

from benchmark import flops
from benchmark import reference as ref

LN_EPS = 1e-5


def _model_config(config, traffic):
    from paddle_tpu.models import bert
    return bert.BertConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        ff_size=config["intermediate_size"],
        max_position=config["max_position_embeddings"],
        type_vocab_size=config["type_vocab_size"],
        hidden_dropout=config["hidden_dropout_prob"],
        attn_dropout=config["attention_probs_dropout_prob"],
        initializer_range=config["initializer_range"],
        dtype=config["precision"], attn_impl="auto")


def build(config, traffic, optimizer_fn):
    """(main, startup, loss variable) of the training program."""
    from paddle_tpu.models import bert
    main, startup, _feeds, fetch = bert.bert_pretrain_program(
        _model_config(config, traffic), batch_rows(traffic),
        traffic["seq_len"], traffic["max_predictions"],
        optimizer_fn=optimizer_fn)
    return main, startup, fetch["loss"]


def batch_rows(traffic):
    return traffic["global_batch"]


def tokens_per_step(traffic):
    return batch_rows(traffic) * traffic["seq_len"]


def param_specs(config, traffic):
    """name -> (shape, dtype in the program, "normal" | "ones" | "zeros"),
    as google-research/bert initialises them: truncated normal of
    `initializer_range` for matrices and tables, ones and zeros for
    layer-norm scales and all biases. Encoder matrices and biases are held
    in `precision`, the rest in float32, as the program holds them."""
    h, ff = config["hidden_size"], config["intermediate_size"]
    enc = config["precision"]
    specs = {
        "word_embedding": ((config["vocab_size"], h), "float32", "normal"),
        "pos_embedding": ((config["max_position_embeddings"], h),
                          "float32", "normal"),
        "sent_embedding": ((config["type_vocab_size"], h), "float32",
                           "normal"),
        "pre_encoder_ln_s": ((h,), "float32", "ones"),
        "pre_encoder_ln_b": ((h,), "float32", "zeros"),
    }
    for i in range(config["num_hidden_layers"]):
        p = "encoder_layer_%d_" % i
        for fc in ("query", "key", "value", "output"):
            specs[p + "multi_head_att_%s_fc.w_0" % fc] = ((h, h), enc,
                                                          "normal")
            specs[p + "multi_head_att_%s_fc.b_0" % fc] = ((h,), enc, "zeros")
        specs[p + "ffn_fc_0.w_0"] = ((h, ff), enc, "normal")
        specs[p + "ffn_fc_0.b_0"] = ((ff,), enc, "zeros")
        specs[p + "ffn_fc_1.w_0"] = ((ff, h), enc, "normal")
        specs[p + "ffn_fc_1.b_0"] = ((h,), enc, "zeros")
        for ln in ("post_att_ln", "post_ffn_ln"):
            specs[p + ln + "_s"] = ((h,), "float32", "ones")
            specs[p + ln + "_b"] = ((h,), "float32", "zeros")
    specs.update({
        "pooled_fc.w_0": ((h, h), "float32", "normal"),
        "pooled_fc.b_0": ((h,), "float32", "zeros"),
        "mask_lm_trans_fc.w_0": ((h, h), "float32", "normal"),
        "mask_lm_trans_fc.b_0": ((h,), "float32", "zeros"),
        "mask_lm_trans_ln_s": ((h,), "float32", "ones"),
        "mask_lm_trans_ln_b": ((h,), "float32", "zeros"),
        "mask_lm_out_fc.b_0": ((config["vocab_size"],), "float32", "zeros"),
        "next_sent_fc.w_0": ((h, 2), "float32", "normal"),
        "next_sent_fc.b_0": ((2,), "float32", "zeros"),
    })
    return specs


def make_batch(config, traffic, rng):
    """One random-but-valid pre-training batch from `rng`
    (numpy.random.Generator): every row differs."""
    n, t, preds = batch_rows(traffic), traffic["seq_len"], \
        traffic["max_predictions"]
    vocab = config["vocab_size"]
    src = rng.integers(0, vocab, (n, t, 1), dtype=np.int64)
    pos = np.tile(np.arange(t, dtype=np.int64).reshape(1, t, 1), (n, 1, 1))
    sent = np.zeros((n, t, 1), np.int64)
    sent[:, t // 2:, :] = 1
    mask = np.ones((n, t, 1), np.float32)
    picks = np.stack([rng.choice(t, preds, replace=False) + i * t
                      for i in range(n)])
    return {"src_ids": src, "pos_ids": pos, "sent_ids": sent,
            "input_mask": mask,
            "mask_pos": picks.reshape(-1, 1).astype(np.int64),
            "mask_label": rng.integers(0, vocab, (n * preds, 1),
                                       dtype=np.int64),
            "labels": rng.integers(0, 2, (n, 1), dtype=np.int64)}


def train_flops(config, traffic):
    return flops.bert_train_flops(
        config["hidden_size"], config["num_hidden_layers"],
        config["intermediate_size"], config["vocab_size"],
        batch_rows(traffic), traffic["seq_len"], traffic["max_predictions"])


def attention_calls(config, traffic):
    """No Pallas attention at this length: the program's `auto` sends
    T*T <= 256*256 to XLA."""
    return []


# ---- the plain reference -------------------------------------------------

def block_of(batch, lo, hi):
    """Rows lo..hi of a host batch, prediction positions re-based."""
    n, t = batch["src_ids"].shape[:2]
    preds = batch["mask_pos"].shape[0] // n
    sl = slice(lo * preds, hi * preds)
    return {"src": batch["src_ids"][lo:hi, :, 0].astype(np.int32),
            "pos": batch["pos_ids"][lo:hi, :, 0].astype(np.int32),
            "sent": batch["sent_ids"][lo:hi, :, 0].astype(np.int32),
            "mask": batch["input_mask"][lo:hi, :, 0],
            "mask_pos": (batch["mask_pos"][sl, 0] - lo * t).astype(np.int32),
            "mask_label": batch["mask_label"][sl, 0].astype(np.int32),
            "nsp": batch["labels"][lo:hi, 0].astype(np.int32)}


def reference_loss(p, blk, config, traffic, mm):
    """This block's contribution to the batch's loss (MLM mean over all
    the batch's prediction slots + NSP mean over all its rows)."""
    import jax.numpy as jnp
    import jax
    h, heads = config["hidden_size"], config["num_attention_heads"]
    dh = h // heads
    rows_total = batch_rows(traffic)
    n, t = blk["src"].shape

    x = p["word_embedding"][blk["src"]] + p["pos_embedding"][blk["pos"]] \
        + p["sent_embedding"][blk["sent"]]
    x = ref.layer_norm(x, p["pre_encoder_ln_s"], p["pre_encoder_ln_b"],
                       LN_EPS)
    bias = ((blk["mask"] - 1.0) * 10000.0)[:, None, None, :]   # (n,1,1,t)

    def split(y):
        return y.reshape(n, t, heads, dh).transpose(0, 2, 1, 3)

    for i in range(config["num_hidden_layers"]):
        q = "encoder_layer_%d_" % i
        a = q + "multi_head_att_"
        qh = split(mm(x, p[a + "query_fc.w_0"]) + p[a + "query_fc.b_0"])
        kh = split(mm(x, p[a + "key_fc.w_0"]) + p[a + "key_fc.b_0"])
        vh = split(mm(x, p[a + "value_fc.w_0"]) + p[a + "value_fc.b_0"])
        scores = mm(qh, kh.transpose(0, 1, 3, 2)) * dh ** -0.5 + bias
        ctx = mm(jax.nn.softmax(scores, axis=-1), vh)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(n, t, h)
        att = mm(ctx, p[a + "output_fc.w_0"]) + p[a + "output_fc.b_0"]
        x = ref.layer_norm(x + att, p[q + "post_att_ln_s"],
                           p[q + "post_att_ln_b"], LN_EPS)
        ff = ref.gelu(mm(x, p[q + "ffn_fc_0.w_0"]) + p[q + "ffn_fc_0.b_0"])
        ff = mm(ff, p[q + "ffn_fc_1.w_0"]) + p[q + "ffn_fc_1.b_0"]
        x = ref.layer_norm(x + ff, p[q + "post_ffn_ln_s"],
                           p[q + "post_ffn_ln_b"], LN_EPS)

    pooled = jnp.tanh(mm(x[:, 0, :], p["pooled_fc.w_0"])
                      + p["pooled_fc.b_0"])
    picked = x.reshape(n * t, h)[blk["mask_pos"]]
    trans = ref.gelu(mm(picked, p["mask_lm_trans_fc.w_0"])
                     + p["mask_lm_trans_fc.b_0"])
    trans = ref.layer_norm(trans, p["mask_lm_trans_ln_s"],
                           p["mask_lm_trans_ln_b"], LN_EPS)
    logits = mm(trans, p["word_embedding"].T) + p["mask_lm_out_fc.b_0"]
    mlm = ref.cross_entropy(logits, blk["mask_label"])
    nsp_logits = mm(pooled, p["next_sent_fc.w_0"]) + p["next_sent_fc.b_0"]
    nsp = ref.cross_entropy(nsp_logits, blk["nsp"])
    return (jnp.sum(mlm) / (rows_total * traffic["max_predictions"])
            + jnp.sum(nsp) / rows_total)
