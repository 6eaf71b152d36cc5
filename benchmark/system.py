"""Everything the benchmark takes from the program, in one place: the pool of
featurized structures, the model, and a state built around the benchmark's own
seeded weights. Kinds import the program's drivers themselves; nothing here
measures anything.
"""

from __future__ import annotations

import importlib
import os
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# featurized pools, one file a (generator, n, pool_seed): only a checkout's
# first run of a configuration builds it (~80 KB a structure, gitignored)
POOL_DIR = os.path.join(HERE, ".cache")


def featurize_config(config: dict):
    from cgnn_tpu.config import DataConfig

    return DataConfig(**config["featurize"]).featurize_config()


def load_pool(config: dict) -> tuple[list, dict]:
    """The configuration's fixed data set -> (graphs, {"built": bool,
    "seconds": float}). The pool never depends on ``--seed``: bucket
    capacities and shape ladders are planned from it, so a pool drawn from
    the seed would give every seed its own compiled shapes."""
    from cgnn_tpu.data import dataset
    from cgnn_tpu.data.cache import load_graph_cache, save_graph_cache

    data = config["data"]
    t0 = time.perf_counter()
    tag = "{}-{}-{}-g{}".format(data["generator"], data["n"],
                                data["pool_seed"],
                                int(bool(data.get("keep_geometry"))))
    path = os.path.join(POOL_DIR, tag + ".npz")
    if os.path.exists(path):
        graphs = load_graph_cache(path)
        built = False
    else:
        kw = {"keep_geometry": True} if data.get("keep_geometry") else {}
        graphs = getattr(dataset, data["generator"])(
            int(data["n"]), featurize_config(config),
            seed=int(data["pool_seed"]), **kw)
        os.makedirs(POOL_DIR, exist_ok=True)
        tmp = path + ".tmp.npz"
        save_graph_cache(graphs, tmp)
        os.replace(tmp, path)
        built = True
    return graphs, {"built": built, "seconds": time.perf_counter() - t0}


def build_model(config: dict):
    """The model through the builder function the configuration names."""
    from cgnn_tpu.config import DataConfig, ModelConfig

    mod, fn = config["builder"].split(":")
    builder = getattr(importlib.import_module(mod), fn)
    model_cfg = ModelConfig(
        dtype=config["precision"]["compute"],
        dense_m=int(config["layout"]["dense_m"]),
        **{k: config["model"][k] for k in (
            "atom_fea_len", "n_conv", "h_fea_len", "n_h", "num_targets")},
    )
    return builder(model_cfg, DataConfig(**config["featurize"]),
                   "regression", log_fn=print)


def edge_dtype(config: dict):
    import jax.numpy as jnp

    return (jnp.bfloat16 if config["precision"]["compute"] == "bfloat16"
            else np.float32)


def target_stats(graphs) -> tuple[float, float]:
    """Mean and std of the pool's targets (the standardisation the loss
    uses), computed here so that the reference takes no scale of the
    program's."""
    t = np.array([float(np.atleast_1d(g.target)[0]) for g in graphs],
                 np.float64)
    return float(t.mean()), float(max(t.std(), 1e-6))


def build_state(config: dict, model, params, batch_stats, t_mean: float,
                t_std: float, steps_per_epoch: int = 1):
    """A TrainState around the benchmark's weights, with the optimizer the
    configuration's ``train`` block names (train.py's defaults)."""
    import jax
    import jax.numpy as jnp

    from cgnn_tpu.train import Normalizer, make_optimizer
    from cgnn_tpu.train.state import TrainState

    tr = config["train"]
    tx = make_optimizer(
        optim=tr["optim"], lr=tr["lr"], momentum=tr["momentum"],
        lr_milestones=[m * steps_per_epoch
                       for m in tr["lr_milestones_epochs"]],
    )
    normalizer = Normalizer(mean=jnp.asarray([t_mean], jnp.float32),
                            std=jnp.asarray([t_std], jnp.float32))
    return TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        batch_stats=batch_stats, opt_state=tx.init(params),
        normalizer=normalizer, rng=jax.random.key(0),
        apply_fn=model.apply, tx=tx,
    )


def graph_as_ref(g) -> dict:
    """A pool structure as the reference reads it (the data set's own
    features; no table or scale of the program's)."""
    return {"atom_fea": g.atom_fea, "edge_fea": g.edge_fea,
            "centers": g.centers, "neighbors": g.neighbors,
            "target": g.target}
