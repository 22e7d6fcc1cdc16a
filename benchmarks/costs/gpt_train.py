"""Operations one training step of a GPT needs for each token, by the
usual count: 6 * N for the matrix products of forward and backward over
N parameters (the position table left out: it is looked up, not
multiplied; the tied head counts once, as the head), plus attention's
12 * L * S * H with the causal half not discounted (PaLM's convention).
Recomputed operations do not count."""


def params_without_positions(cfg):
    V, H, L = cfg["vocab_size"], cfg["hidden_size"], cfg["num_layers"]
    F = cfg["ffn_hidden_size"]
    per_layer = (H * 3 * H + 3 * H) + (H * H + H) + (H * F + F) \
        + (F * H + H) + 4 * H
    return V * H + L * per_layer + 2 * H


def ops_per_token(cfg, seq):
    return 6 * params_without_positions(cfg) \
        + 12 * cfg["num_layers"] * seq * cfg["hidden_size"]
