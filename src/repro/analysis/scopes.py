"""The layers of the train step, as named scopes and as HLO ``op_name``s.

The trainer opens each named scope below with ``jax.named_scope``; JAX
writes the scope into the ``op_name`` metadata of every HLO instruction
traced inside it, and XLA keeps that metadata through compilation.
``classify`` maps one ``op_name`` back to its layer, and
``instruction_scopes`` maps every instruction of a compiled module, so a
device trace (whose events carry instruction names) can be summed per
layer (DESIGN.md §15).

Named scopes (opened in ``train/steps.py`` and ``core/zen.py``):

  fwd            ``model.train_loss`` inside ``value_and_grad``
  sync.encode    GradSync's local work: bucket packing and unpacking, the
                 compress hook, ``_encode_bucket``
  sync.exchange  ``GradSync._commit_bucket`` (and the hierarchical intra
                 stage): the per-bucket collectives and decode
  opt            grad clip and the (ZeRO-1) optimizer update
  zero1.gather   the all-gather of the updated parameter chunks (inside opt)

Derived layers, named by JAX's transformations:

  bwd            ops under ``transpose(...)``, the backward pass
  remat          ops under ``rematted_computation``: the forward recomputed
                 inside the backward by ``jax.checkpoint``
  unscoped       everything else, and instructions with no ``op_name``

A second map, orthogonal to the layers, names the model's blocks whose
time a cell reads (``instruction_blocks``): the innermost block scope of
an instruction, in whichever layer it runs (fwd, remat or, through
``transpose(...)``, bwd), else ``other``:

  moe.route      the router, top-k, sort, dispatch gather, combine and
                 balance loss of an expert layer (``models/moe.py``)
  moe.experts    the held experts' grouped matmuls

This module imports nothing from the trainer, so ``core/`` can use it.
"""
from __future__ import annotations

import re

from repro.analysis.hlo_ir import HloModule

FWD = "fwd"
BWD = "bwd"
REMAT = "remat"
OPT = "opt"
SYNC_ENCODE = "sync.encode"
SYNC_EXCHANGE = "sync.exchange"
ZERO1_GATHER = "zero1.gather"
UNSCOPED = "unscoped"

MOE_ROUTE = "moe.route"
MOE_EXPERTS = "moe.experts"
OTHER = "other"

NAMED = (FWD, SYNC_ENCODE, SYNC_EXCHANGE, OPT, ZERO1_GATHER)
BLOCKS = (MOE_ROUTE, MOE_EXPERTS)
ALL = (FWD, BWD, REMAT, OPT, SYNC_ENCODE, SYNC_EXCHANGE, ZERO1_GATHER, UNSCOPED)
REMAT_MARK = "rematted_computation"
# a scope seen through a transformation: ``jvp(fwd)`` is ``fwd``
_WRAPPED = re.compile(r"^[\w.]+\((.*)\)$")


def _bare(part: str) -> str:
    while (m := _WRAPPED.match(part)):
        part = m.group(1)
    return part


def classify(op_name: str) -> str:
    """The layer of one ``op_name`` (``a/b/c`` path of JAX scopes)."""
    parts = op_name.split("/")
    if REMAT_MARK in parts:
        return REMAT
    if any(p.startswith("transpose(") for p in parts):
        return BWD
    for p in map(_bare, reversed(parts)):      # innermost named scope wins
        if p in NAMED:
            return p
    return UNSCOPED


def block_of(op_name: str) -> str:
    """The block of one ``op_name``: its innermost block scope, seen
    through any transformation, else ``other``."""
    for p in map(_bare, reversed(op_name.split("/"))):
        if p in BLOCKS:
            return p
    return OTHER


def instruction_scopes(hlo_text: str) -> dict:
    """``{instruction name: layer}`` for every instruction of a compiled
    HLO module (``compiled.as_text()``).  An instruction that XLA made and
    gave no ``op_name`` takes, if it is a fusion, the last ``op_name``
    inside its fused computation (the one nearest its root), and otherwise
    the layer of the instruction that calls its computation (a loop body
    takes its ``while``'s); in the entry computation it is unscoped."""
    return _instruction_map(hlo_text, classify, UNSCOPED)


def instruction_blocks(hlo_text: str) -> dict:
    """``{instruction name: block}`` for every instruction of a compiled
    HLO module, by the rules of ``instruction_scopes`` with ``block_of``
    in place of ``classify``; ``other`` where no block scope is found."""
    return _instruction_map(hlo_text, block_of, OTHER)


def _instruction_map(hlo_text: str, name_of, default: str) -> dict:
    module = HloModule.parse(hlo_text)
    caller = {c: op for comp in module.computations.values()
              for op in comp.ops for c in op.called}
    home = {op.name: comp for comp, op in module.all_ops()}
    memo: dict = {}

    def layer(op) -> str:
        if op.name in memo:
            return memo[op.name]
        memo[op.name] = default           # a cycle cannot recurse forever
        name = op.op_name
        if not name and op.kind == "fusion" and op.called:
            fused = module.computations.get(op.called[0])
            inner = [o.op_name for o in (fused.ops if fused else ()) if o.op_name]
            name = inner[-1] if inner else ""
        if name:
            memo[op.name] = name_of(name)
        elif home[op.name] in caller:
            memo[op.name] = layer(caller[home[op.name]])
        return memo[op.name]

    return {op.name: layer(op) for _, op in module.all_ops()}
