"""Model phases of a compiled step: one vocabulary, defined here.

The device profiler names what ran by HLO instruction (``fusion.652``), which
says nothing to a reader of the model. The compiled program knows better:
every instruction's metadata carries the ``op_name`` JAX built from the name
stack — flax module scopes (``conv_1/bn1``), transforms (``jvp``,
``transpose``) and the ``jax.named_scope`` s the program adds where flax says
nothing (models/cgcnn.py, models/forcefield.py, train/step.py,
train/force_step.py, resilience/guard.py, data/compact.py, train/loop.py,
models/sdar.py, models/afmoe.py, models/lfm2.py, models/nemotron_h.py,
models/lm_blocks.py, ops/moe.py, ops/prepare_heads.py, train/lm_step.py). ``classify`` maps such a path to one phase
of ``PHASES`` and a direction; ``phase_table`` does so for every instruction
of an optimized HLO module that the device can report as an event.

Scopes are metadata only: they change no compiled code (pinned by
tests/test_observe.py). The persistent compile cache keys on the program
and not on its metadata, so a program cached before a scope was added or
renamed comes back with the old names: clear the cache after touching one.
"""

from __future__ import annotations

import re

# scopes the program opens by name (jax.named_scope); the rest of the
# vocabulary is read off flax module names below
EXPAND = "expand"
EMBED = "embed"
EDGE_GEOM = "edge_geom"  # models/forcefield.py: positions -> edge features
CONV_GATHER = "conv.gather"
CONV_FC_FULL = "conv.fc_full"
CONV_BN1 = "conv.bn1"
CONV_GATE = "conv.gate"
CONV_AGGREGATE = "conv.aggregate"
CONV_BN2 = "conv.bn2"
# models/cgcnn.py: LayerNorm after the neighbour sum where a model has it in
# bn2's place (the Open Catalyst CGCNN), with the residual and its softplus
CONV_LN = "conv.ln"
POOL_HEAD = "pool_head"
FORCE_READOUT = "force_readout"  # models/forcefield.py: per-atom energies
LOSS = "loss"
OPTIMIZER = "optimizer"
SCAN = "scan"
# train/step.py: the pmean/psum of gradients, statistics and metric sums
# under data parallelism (the one phase a one-chip program never has)
DP_ALLREDUCE = "dp.allreduce"
# models/sdar.py, ops/moe.py, train/lm_step.py: the block-diffusion
# mixture-of-experts decoder. ``attn.proj`` is the projections, the head
# norms, RoPE and ``W_o``; ``attn.bd`` the masked attention alone;
# ``moe.route`` the router, the top-k, the sort and the rows' way out and
# back; ``moe.expert`` the grouped matmuls; ``lm.head`` the final norm, the
# head and the loss
LM_EMBED = "lm.embed"
ATTN_PROJ = "attn.proj"
ATTN_BD = "attn.bd"
MOE_ROUTE = "moe.route"
MOE_EXPERT = "moe.expert"
LM_HEAD = "lm.head"
# models/afmoe.py: the window-and-full mixture-of-experts decoder. Its masked
# attention alone by layer kind (``attn.proj`` takes its gate too), the
# leading dense layer's MLP and the shared expert, each with its layer's two
# MLP norms (``moe.shared`` also the sum with the routed rows: ``moe.route``
# stays the router and the rows' way out and back); the held experts' weight
# casts are under ``moe.expert`` there, the biases' update under
# ``optimizer``
ATTN_WINDOW = "attn.window"
ATTN_FULL = "attn.full"
MLP_DENSE = "mlp.dense"
MOE_SHARED = "moe.shared"
# models/lfm2.py: the hybrid decoder's convolution layers. ``sconv.proj`` is
# the layer's first norm, ``W_in`` and ``W_out``; ``sconv.mix`` the gates and
# the three taps alone (ops/short_conv.py). Its attention layer is under
# ``attn.proj`` / ``attn.full``, its dense MLP under ``mlp.dense``, its
# experts under ``moe.route`` (the layer's second norm and the residual sum
# too) and ``moe.expert``
SCONV_PROJ = "sconv.proj"
SCONV_MIX = "sconv.mix"
# models/nemotron_h.py: the hybrid decoder's Mamba-2 layers. ``ssm.proj`` is
# the layer's norm, ``W_in`` and ``W_out``; ``ssm.conv`` the four taps, their
# bias and the ``silu`` (ops/short_conv.py); ``ssm.scan`` the steps ``dt``,
# the decays, the chunks' products, the recurrence over the chunks' states
# and the skip ``D`` (ops/ssd.py); ``ssm.gate`` the gate and the grouped
# norm. Its attention layers are under ``attn.proj`` / ``attn.full``, its
# expert layers under ``moe.route`` (the layer's norm and the residual sum
# too), ``moe.expert`` and ``moe.shared``
SSM_PROJ = "ssm.proj"
SSM_CONV = "ssm.conv"
SSM_SCAN = "ssm.scan"
SSM_GATE = "ssm.gate"
OTHER = "other"

PHASES = (EXPAND, EMBED, EDGE_GEOM, CONV_GATHER, CONV_FC_FULL, CONV_BN1,
          CONV_GATE, CONV_AGGREGATE, CONV_BN2, CONV_LN, POOL_HEAD,
          FORCE_READOUT, LOSS, OPTIMIZER, SCAN, DP_ALLREDUCE, LM_EMBED,
          ATTN_PROJ, ATTN_BD, MOE_ROUTE, MOE_EXPERT, LM_HEAD, ATTN_WINDOW,
          ATTN_FULL, MLP_DENSE, MOE_SHARED, SCONV_PROJ, SCONV_MIX, SSM_PROJ,
          SSM_CONV, SSM_SCAN, SSM_GATE, OTHER)
FWD, BWD, BWD2 = "fwd", "bwd", "bwd2"

# a path component -> its phase: the named scopes themselves, and the flax
# module names of models/cgcnn.py (bn1/bn2/ln/fc_full exist only inside a conv)
_TOKEN_PHASE = {p: p for p in PHASES if p != OTHER} | {
    "embedding": EMBED,
    "fc_full": CONV_FC_FULL,
    "bn1": CONV_BN1,
    "bn2": CONV_BN2,
    "ln": CONV_LN,
    "conv_to_fc": POOL_HEAD,
    "fc_out": POOL_HEAD,
}
_HEAD_FC = re.compile(r"fc_\d+$")
_LOOP = {"while", "body", "cond"}


def classify(op_name: str) -> tuple[str, str]:
    """``op_name`` (an instruction's metadata path) -> (phase, direction).

    The innermost component that names a phase wins. A path that is only the
    loop's own machinery (``jit(f)/while/body/dynamic_slice``) is ``scan``;
    anything else without a phase is ``other``.

    The direction counts the ``transpose(`` s on the path, the reverse passes
    of autodiff the instruction lies under: none is ``fwd``, one is ``bwd``,
    two or more ``bwd2``. A first-order step has the first two. The force
    step (train/force_step.py) differentiates twice, and its paths read

        jvp(jvp(Model))                        fwd   the energies
        jvp(transpose(jvp(Model)))             bwd   the inner reverse pass:
                                                     the forces, -dE/dx
        transpose(jvp(jvp(Model)))             bwd   the outer reverse pass
                                                     over the energies
        transpose(jvp(transpose(jvp(Model))))  bwd2  the outer reverse pass
                                                     over the forces

    so ``bwd`` there is every first derivative and ``bwd2`` is the second
    derivative, which only a loss on the forces pays for. (jnp's own
    ``transpose`` primitive has no parenthesis and counts as nothing.)
    """
    # XLA joins the names of instructions it merged with ';': the first is
    # the one the merged instruction mostly is
    op_name = op_name.partition(";")[0]
    direction = (FWD, BWD, BWD2)[min(op_name.count("transpose("), 2)]
    # jit(f)/transpose(jvp(Model))/conv_1/bn1/mul -> components
    parts = [t for t in re.split(r"[/()]", op_name) if t]
    for tok in reversed(parts):
        phase = _TOKEN_PHASE.get(tok)
        if phase is None and _HEAD_FC.match(tok):
            phase = POOL_HEAD
        if phase is not None:
            return phase, direction
    if parts[:1] == ["jit"]:
        parts = parts[2:]  # the wrapper and the function's name
    rest = [t for t in parts if t not in _LOOP]
    if len(rest) < len(parts) and len(rest) <= 1:
        return SCAN, direction
    return OTHER, direction


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(r"\b(calls|to_apply)=%?([\w.\-]+)")
# `` copy(%fusion.648)`` after the result's type; a layout's ``T(8,128)``
# follows no blank
_OPERANDS = re.compile(r"\s[a-z][\w\-]*\(([^()]*)\)")
_OPERAND = re.compile(r"%([\w.\-]+)")


def _parse(hlo_text: str) -> dict:
    """{computation: {"instrs": {name: rest of its text}, "root": name}}.

    An instruction is one line, but for a kernel's custom call: its
    ``backend_config`` runs over several lines, the last of which (``}},
    metadata={op_name=...}``) starts in the first column as a computation's
    closing brace does. Only a line that is a brace alone closes a
    computation; any other line that opens no instruction belongs to the
    instruction above it (its ``op_name`` is there)."""
    comps: dict = {}
    cur = last = None
    for line in hlo_text.splitlines():
        if cur is None:
            m = _COMPUTATION.match(line)
            if m:
                cur = comps[m.group(1)] = {"instrs": {}, "root": None}
                last = None
            continue
        if line.rstrip() == "}":
            cur = None
            continue
        m = _INSTRUCTION.match(line)
        if m:
            last = m.group(2)
            cur["instrs"][last] = m.group(3)
            if m.group(1):
                cur["root"] = last
        elif last is not None:
            cur["instrs"][last] += " " + line.strip()
    return comps


def _resolve(comps: dict, comp: str, name: str, depth: int = 0) -> str:
    """An instruction's op_name: its own; else, for one that calls a
    computation (a fusion), that computation's root's; else its operands',
    the first that has one. So what only moves data (a layout copy or a
    prefetch the compiler inserted, a get-tuple-element, a tuple) counts
    with the phase that produced the data; what hangs on nothing but a
    parameter has no name."""
    rest = comps[comp]["instrs"].get(name)
    if rest is None or depth > 8:
        return ""
    m = _OP_NAME.search(rest)
    if m:
        return m.group(1)
    called = _CALLED.search(rest)
    if called and called.group(2) in comps:
        inner = comps[called.group(2)]
        if inner["root"]:
            return _resolve(comps, called.group(2), inner["root"], depth + 1)
    operands = _OPERANDS.search(rest)
    for operand in _OPERAND.findall(operands.group(1) if operands else ""):
        found = _resolve(comps, comp, operand, depth + 1)
        if found:
            return found
    return ""


def phase_table(compiled_hlo_text: str) -> dict:
    """Optimized HLO text (``compiled.as_text()``) -> {instruction name:
    (phase, direction)} for every instruction the device can report as an
    event of its own: those of the entry computation and of loop bodies and
    conditions, not those inside a fusion or a reducer.

    A fusion takes its own ``op_name`` (XLA copies it from the fusion's root)
    and, where it has none, its root's; an instruction that has none and
    calls nothing (a copy the compiler inserted) takes its operand's
    (``_resolve``); one with no name to be found is ``other``.
    """
    comps = _parse(compiled_hlo_text)
    nested = set()
    for comp in comps.values():
        for rest in comp["instrs"].values():
            for kind, target in _CALLED.findall(rest):
                if kind == "calls" or " call(" not in rest:
                    nested.add(target)
    table = {}
    for cname, comp in comps.items():
        if cname in nested:
            continue
        for name in comp["instrs"]:
            op_name = _resolve(comps, cname, name)
            table[name] = classify(op_name) if op_name else (OTHER, FWD)
    return table
