"""Model FLOPs of one sample through the encoder-decoder family (the
Whisper backbone), counted from shapes: ``encoder_seq`` frames through the
encoder, ``seq`` decoder tokens through the decoder and the vocabulary
projection.  Same conventions as `flops/dense.py`: matrix products only,
full attention scores, training = 3 x forward."""


def forward(cfg: dict, seq: int) -> float:
    d, hd, ff, V = cfg["d_model"], cfg["head_dim"], cfg["d_ff"], cfg["vocab_size"]
    qd, kvd = cfg["num_heads"] * hd, cfg["num_kv_heads"] * hd
    se = cfg["encoder_seq"]
    mlp = lambda s: 2 * s * d * ff * (3 if cfg["mlp_type"] == "swiglu" else 2)
    self_attn = lambda s: 2 * s * d * (2 * qd + 2 * kvd) + 2 * 2 * s * s * qd
    cross = 2 * seq * d * 2 * qd + 2 * se * d * 2 * kvd + 2 * 2 * seq * se * qd
    enc = cfg["encoder_layers"] * (self_attn(se) + mlp(se))
    dec = cfg["num_layers"] * (self_attn(seq) + cross + mlp(seq))
    return float(enc + dec + 2 * seq * d * V)


def train(cfg: dict, seq: int) -> float:
    return 3.0 * forward(cfg, seq)
