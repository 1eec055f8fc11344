"""Model FLOPs of one sequence through the dense decoder-only family,
counted from shapes: every matrix product at 2 operations per multiply-add,
attention over the full S x S scores (the convention of PaLM's MFU), the
embedding lookup free.  Norms, activations and the softmax are not
counted.  A training step's forward and backward take three times the
forward; recomputation under remat does not count."""


def forward(cfg: dict, seq: int) -> float:
    d, hd, ff, V = cfg["d_model"], cfg["head_dim"], cfg["d_ff"], cfg["vocab_size"]
    qd, kvd = cfg["num_heads"] * hd, cfg["num_kv_heads"] * hd
    proj = 2 * seq * d * (2 * qd + 2 * kvd)
    scores = 2 * 2 * seq * seq * qd
    mlp = 2 * seq * d * ff * (3 if cfg["mlp_type"] == "swiglu" else 2)
    return float(cfg["num_layers"] * (proj + scores + mlp) + 2 * seq * d * V)


def train(cfg: dict, seq: int) -> float:
    return 3.0 * forward(cfg, seq)
