"""Model FLOPs of one sequence through the mixture-of-experts decoder family
(OLMoE), counted from shapes with the conventions of `flops/dense.py`:
every matrix product at 2 operations per multiply-add (the router's
included), attention over the full S x S scores, the embedding lookup
free, training = 3 x forward.  The routed experts count at the expected
``experts_per_token * num_experts / router_experts`` held assignments a
token, each a SwiGLU expert of 3 d x ff products."""


def forward(cfg: dict, seq: int) -> float:
    d, hd, ff, V = cfg["d_model"], cfg["head_dim"], cfg["d_ff"], cfg["vocab_size"]
    qd, kvd = cfg["num_heads"] * hd, cfg["num_kv_heads"] * hd
    R = cfg["router_experts"]
    proj = 2 * seq * d * (2 * qd + 2 * kvd)
    scores = 2 * 2 * seq * seq * qd
    router = 2 * seq * d * R
    held = cfg["experts_per_token"] * cfg["num_experts"] / R
    experts = held * 2 * seq * d * ff * 3
    return float(cfg["num_layers"] * (proj + scores + router + experts)
                 + 2 * seq * d * V)


def train(cfg: dict, seq: int) -> float:
    return 3.0 * forward(cfg, seq)
