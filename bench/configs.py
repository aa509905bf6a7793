"""A configuration file (``bench/configs/<name>.json``) as the program's
``ModelConfig`` and as the plain sizes the reference and the FLOP counts use.

The files keep the published config's own key names, so that ``reduced``
in BENCHMARK.json can be read against the source, with the published
values; ``program`` holds what the repo's model takes in their place and
the few non-size choices it makes (family, norm, MLP, position scheme,
norm epsilon, biases), and is read first.  Each size is read through the
first of its aliases present.
"""
from __future__ import annotations

_ALIASES = {
    "num_layers": ("num_hidden_layers", "n_layer"),
    "d_model": ("hidden_size", "n_embd"),
    "num_heads": ("num_attention_heads", "n_head"),
    "num_kv_heads": ("num_key_value_heads",),
    "d_ff": ("intermediate_size", "n_inner"),
    "vocab_size": ("vocab_size",),
    "max_seq": ("max_position_embeddings", "n_positions"),
    "rope_theta": ("rope_theta",),
    "norm_eps": ("norm_epsilon", "layer_norm_epsilon", "rms_norm_eps"),
}


def _pick(conf: dict, field: str):
    prog = conf.get("program", {})
    if field in prog:
        return prog[field]
    for key in _ALIASES.get(field, ()):
        if key in conf:
            return conf[key]
    raise KeyError(f"configuration has no {field} "
                   f"(looked for {_ALIASES.get(field, (field,))})")


def sizes(conf: dict) -> dict:
    """The plain sizes: layers, widths, heads, vocab, norm epsilon."""
    heads = _pick(conf, "num_heads")
    d = _pick(conf, "d_model")
    try:
        kv = _pick(conf, "num_kv_heads")
    except KeyError:
        kv = heads
    prog = conf.get("program", {})
    return {"num_layers": _pick(conf, "num_layers"), "d_model": d,
            "num_heads": heads, "num_kv_heads": kv,
            "head_dim": prog.get("head_dim", d // heads),
            "d_ff": _pick(conf, "d_ff"),
            "vocab_size": _pick(conf, "vocab_size"),
            "max_seq": _pick(conf, "max_seq"),
            "rope_theta": float(_pick(conf, "rope_theta")),
            "norm_eps": float(_pick(conf, "norm_eps")),
            "norm": prog.get("norm", "layernorm"),
            "mlp": prog.get("mlp", "gelu"),
            "pos_embed": prog.get("pos_embed", "rope"),
            "tied": bool(conf.get("tie_word_embeddings", True))}


def model_config(conf: dict, name: str):
    """The program's ``ModelConfig`` for this file."""
    from repro.models.config import ModelConfig
    s = sizes(conf)
    prog = conf.get("program", {})
    return ModelConfig(
        arch_id=name, family=prog.get("family", "dense"),
        num_layers=s["num_layers"], d_model=s["d_model"],
        num_heads=s["num_heads"], num_kv_heads=s["num_kv_heads"],
        head_dim=s["head_dim"], d_ff=s["d_ff"], vocab_size=s["vocab_size"],
        pos_embed=s["pos_embed"], rope_theta=s["rope_theta"],
        norm=s["norm"], mlp=s["mlp"], tie_embeddings=s["tied"],
        max_seq=s["max_seq"], source=conf.get("source", ""))
