"""A toy family, never a cell: a model whose LEADING layer stands in a stack
of its own, as a dense layer ahead of expert layers does (the tensors of the
two kinds differ, so one layer axis cannot hold both). It exists to walk the
two optional functions a family may define, `stacks` and `program_params`
(`weights.py`), end to end: this file, `configs/tiny-lead.json` and
`tests/test_family_toy_lead.py`.

The graph is `families/mistral.py`'s dense block in every layer, so that the
program can run it today: the tensors are drawn as `lead.<name>` (the first
`lead_layers` layers) and `blocks.<name>` (the `block_layers` after them),
`program_params` lays both into the one stack the program knows, and the
reference walks the layers by their global index, each from its own stack
(`W.layer(weights, i, cfg)`). A family whose stacks really differ hands the
program one entry a stack instead, once the program can scan two.
"""

from __future__ import annotations

import numpy as np

from benchmark import cells
from benchmark import weights as W


def _dense():
    return cells.load_family("mistral")


def stacks(cfg: dict) -> list[tuple[str, int]]:
    return [("lead", cfg["lead_layers"]), ("blocks", cfg["block_layers"])]


def model_spec(cfg: dict):
    """One dense stack of `num_hidden_layers` layers (what a cut holds)."""
    return _dense().model_spec(cfg)


def tensor_shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], bool]]:
    out = {name: s for name, s in _dense().tensor_shapes(cfg).items()
           if name in W.NOT_BLOCKS}
    for prefix, n in stacks(cfg):
        shapes = _dense().tensor_shapes({**cfg, "num_hidden_layers": n})
        out.update({f"{prefix}.{name}": s for name, s in shapes.items()
                    if name not in W.NOT_BLOCKS})
    return out


def program_params(cfg: dict, weights: dict):
    """Both stacks laid end to end into the program's one block stack."""
    prefixes = [p for p, _ in stacks(cfg)]

    def joined(name):
        parts = [weights[f"{p}.{name}"] for p in prefixes]
        if isinstance(parts[0], tuple):
            return tuple(np.concatenate(a) for a in zip(*parts))
        return np.concatenate(parts)

    merged = {n: weights[n] for n in W.NOT_BLOCKS}
    merged.update({n.split(".", 1)[1]: joined(n.split(".", 1)[1])
                   for n in weights if n.startswith(prefixes[0] + ".")})
    return W.to_program_params(merged)


def logits_at(cfg: dict, weights: dict, rows, at, precision: str = "float32",
              flip=None):
    """As `families/mistral.py logits_at` (rows padded to the longest, one
    batch), the layers taken one by one from whichever stack holds them."""
    import jax
    import jax.numpy as jnp

    dense = _dense()
    t = max(len(r) for r in rows)
    tokens = np.full((len(rows), t), 3, np.int64)
    for i, r in enumerate(rows):
        tokens[i, :len(r)] = r
    b_at = np.concatenate([np.full(len(a), i) for i, a in enumerate(at)])
    t_at = np.concatenate([np.asarray(a, np.int64) for a in at])
    eps = cfg.get("rms_norm_eps", 1e-5)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(weights["embedding"])[tokens]
        layer_fn = dense._layer_fn(cfg, precision)
        for i in range(W.depth(weights, cfg)):
            x, _ = layer_fn(x, W.layer(weights, i, cfg), None)
        x = dense._rmsnorm(x[b_at, t_at], jnp.asarray(weights["rms_final"]),
                           eps)
        xr, wr = W.rounder(precision)(x, W.dequantize(*weights["wcls"]))
        out = jnp.einsum("ni,oi->no", xr, wr)
    return (np.asarray(out, np.float32),
            np.full(len(b_at), np.inf, np.float32))
