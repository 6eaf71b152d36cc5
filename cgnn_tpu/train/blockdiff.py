"""``train.py --task blockdiff`` and ``--task lm``: the decoders
(models/sdar.py, block diffusion; models/afmoe.py and models/lfm2.py,
next-token; models/nemotron_h.py, next-token) through ``fit`` and the scan epoch driver, as every task goes:
token batches staged resident, ``TrainState`` and ``make_optimizer``, spans
and phases.

The model is a preset of its task (``PRESETS``, which names the preset's
model too: ``tiny``, ``lfm2-tiny`` and ``nemotron-tiny`` for the CPU; ``sdar-ep8``, one chip's
share of SDAR-30B-A3B-Chat as ``benchmark/configs/sdar-30b-a3b-ep8.json`` has
it; ``trinity-mini-ep16``, one chip's share of Trinity-Mini as
``benchmark/configs/trinity-mini-ep16.json`` has it; ``lfm2-24b-a2b-ep8``,
one chip's share of LFM2-24B-A2B as ``benchmark/configs/
lfm2-24b-a2b-ep8.json`` has it; ``nemotron-3-nano-30b-a3b-ep16``, one chip's
share of NVIDIA-Nemotron-3-Nano-30B-A3B as ``benchmark/configs/
nemotron-3-nano-30b-a3b-ep16.json`` has it) or a JSON file of the fields of the config
dataclass of the task's first model. The data is a synthetic pool of packed
sequences (data/tokens.py): a tokenizer and a corpus reader are not part of
this repo.
"""

from __future__ import annotations

import functools
import importlib
import json

# a model's module under cgnn_tpu.models -> its config dataclass
CONFIGS = {"sdar": "SdarConfig", "afmoe": "AfmoeConfig",
           "lfm2": "Lfm2Config", "nemotron_h": "NemotronHConfig"}
# task -> preset -> (the model, the fields of its config dataclass that
# differ from its defaults); a task's first preset names the model a JSON
# file of fields is read as
PRESETS = {
    "blockdiff": {
        "tiny": ("sdar", dict(
            hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, num_hidden_layers=2, n_experts=16,
            num_experts_per_tok=4, experts_held=(0, 4),
            moe_intermediate_size=32, vocab_size=256, dtype="float32")),
        "sdar-ep8": ("sdar", dict(num_hidden_layers=4)),
    },
    "lm": {
        "tiny": ("afmoe", dict(
            hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, num_hidden_layers=5, num_dense_layers=1,
            sliding_window=16,
            layer_types=("sliding_attention",)
            + ("sliding_attention", "full_attention") * 2,
            intermediate_size=96, moe_intermediate_size=32, n_experts=16,
            num_experts_per_tok=4, experts_held=(0, 4), vocab_size=256,
            dtype="float32")),
        "trinity-mini-ep16": ("afmoe", dict()),  # the dataclass's defaults
        "lfm2-tiny": ("lfm2", dict(
            hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, num_hidden_layers=5, num_dense_layers=1,
            layer_types=("conv",) + ("full_attention", "conv") * 2,
            intermediate_size=96, moe_intermediate_size=32, n_experts=16,
            num_experts_per_tok=4, experts_held=(0, 4), vocab_size=256,
            dtype="float32")),
        "lfm2-24b-a2b-ep8": ("lfm2", dict()),  # the dataclass's defaults
        "nemotron-tiny": ("nemotron_h", dict(
            hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, num_hidden_layers=5,
            hybrid_override_pattern="EMEM*", mamba_num_heads=8,
            mamba_head_dim=8, n_groups=2, ssm_state_size=16, chunk_size=8,
            moe_intermediate_size=24, moe_shared_expert_intermediate_size=48,
            n_experts=16, num_experts_per_tok=4, experts_held=(0, 4),
            vocab_size=256, dtype="float32")),
        # the dataclass's defaults
        "nemotron-3-nano-30b-a3b-ep16": ("nemotron_h", dict()),
    },
}


def model_config(task: str, spec: str, bf16: bool):
    """-> (the model's module, its config dataclass) from a preset's name
    or a JSON file."""
    if spec in PRESETS[task]:
        name, fields = PRESETS[task][spec]
        fields = dict(fields)
    elif any(spec in presets for presets in PRESETS.values()):
        raise ValueError(f"--lm-model {spec} is no preset of --task {task} "
                         f"(its own: {', '.join(PRESETS[task])})")
    else:
        with open(spec) as f:
            fields = json.load(f)
        name = next(iter(PRESETS[task].values()))[0]
        if "experts_held" in fields:
            fields["experts_held"] = tuple(fields["experts_held"])
    if bf16:
        fields["dtype"] = "bfloat16"
    model = importlib.import_module(f"cgnn_tpu.models.{name}")
    return model, getattr(model, CONFIGS[name])(**fields)


def run(args, telemetry, preempt=None, log_fn=print) -> int:
    """The task's ``main``: pool, state, ``fit``. ``args`` are train.py's."""
    import jax
    import jax.numpy as jnp

    from cgnn_tpu.data import tokens
    from cgnn_tpu.train import Normalizer, fit, make_optimizer
    from cgnn_tpu.train.lm_step import make_lm_eval_step, make_lm_train_step
    from cgnn_tpu.train.state import TrainState

    task = args.task
    causal = task == "lm"
    model, cfg = model_config(task, args.lm_model, args.bf16)
    n = args.synthetic or 16
    per_step = args.batch_size
    length = args.lm_seq_len
    block = 1 if causal else cfg.block_length
    pool = tokens.make_pool(
        n, length, vocab_size=cfg.vocab_size, block=block, seed=args.seed,
        doc_median=length / 2, doc_min=max(block, 2), doc_max=length,
        kind="causal" if causal else "blockdiff")
    batches = tokens.split_batches(pool, per_step)
    n_val = max(1, len(batches) // 8)
    train_b, val_b = batches[:-n_val], batches[-n_val:]
    log_fn(f"{task}: {cfg.n_params() / 1e6:.2f} M parameters "
           f"({cfg.num_hidden_layers} layers, experts "
           f"{cfg.experts_held[0]}..{sum(cfg.experts_held) - 1} of "
           f"{cfg.n_experts} held), {n} sequences of {length} tokens, "
           f"{len(train_b)} train / {len(val_b)} val steps of {per_step}")
    tx = make_optimizer(
        optim=args.optim, lr=args.lr, momentum=args.momentum,
        weight_decay=args.weight_decay,
        lr_milestones=[m * len(train_b) for m in args.lr_milestones])
    params = jax.jit(functools.partial(model.init_params, cfg))(
        jax.random.key(args.seed))
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        batch_stats=model.init_stats(cfg) if causal else {},
        opt_state=tx.init(params), normalizer=Normalizer.identity(1),
        rng=jax.random.key(args.seed),
        apply_fn=functools.partial(model.apply, cfg), tx=tx)
    tiles = model.attention_tiles(cfg, length)
    state, result = fit(
        state, [], [], epochs=args.epochs, batch_size=per_step,
        seed=args.seed, print_freq=0, scan_epochs=True,
        packed=(train_b, val_b),
        train_step_fn=make_lm_train_step(cfg, tiles),
        eval_step_fn=make_lm_eval_step(cfg, tiles), best_metric="loss",
        chunk_steps=args.chunk_steps, telemetry=telemetry, preempt=preempt,
        log_fn=log_fn)
    last = result["history"][-1]
    # the step's own numbers (train/lm_step.py ``step_metrics``), whichever
    # the model has
    for name, value in last["train"].items():
        if name not in ("loss", "count"):
            telemetry.set_gauge(name, float(value))
    moved = "expert_bias_abs_max" in last["train"]
    log_fn(f"** best val loss {result['best']:.4f}; a step routed "
           f"{last['train']['moe_rows_here']:.0f} rows to the experts held "
           f"({last['train']['moe_rows_balanced']:.0f} balanced)"
           + (f"; largest bias "
              f"{last['train']['expert_bias_abs_max']:.4f}" if moved
              else ""))
    return 0
