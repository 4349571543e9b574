"""Named scopes of the continual step (DESIGN.md §11): one vocabulary for the
stages that the fused step runs as one XLA program.

Each stage opens ``jax.named_scope(<scope>)`` where its work is written, so
every step builder that calls that code gets the scope. The scope is metadata
only: it lands in the ``op_name`` of each HLO instruction the stage lowers to
(``jit(step)/buffer_update/...``) and changes no value, no RNG draw and no
compiled arithmetic. A profiler trace of the step can then be summed by
stage: ``scope_of`` maps an instruction's ``op_name`` to its stage.

    train           forward and backward of the loss (``value_and_grad``)
    optimizer       the optimizer update
    grad_allreduce  the explicit gradient psum of the manual-DP step
    buffer_update   the Alg-1 push (a tiered store's demotion flush inside it)
    buffer_sample   the local draw of representatives (or exchange candidates)
    exchange        the all_to_all of the global sample
    augment         the concatenation of the batch with its representatives
"""
from __future__ import annotations

import contextlib
import re
from typing import Optional, Set

import jax

STEP_SCOPES = ("train", "optimizer", "grad_allreduce", "buffer_update",
               "buffer_sample", "exchange", "augment")

_WRAPPER = re.compile(r"^[\w.\-]+\((.*)\)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope(name: str) -> contextlib.AbstractContextManager:
    """``jax.named_scope`` of one registered stage."""
    if name not in STEP_SCOPES:
        raise ValueError(f"unknown step scope {name!r}; expected one of {STEP_SCOPES}")
    return jax.named_scope(name)


def _unwrap(part: str) -> str:
    """``transpose(jvp(train))`` -> ``train``: the name inside transform wrappers."""
    while True:
        m = _WRAPPER.match(part)
        if m is None:
            return part
        part = m.group(1)


def scope_of(op_name: Optional[str]) -> Optional[str]:
    """The innermost registered stage in an HLO ``op_name`` path, or None."""
    if not op_name:
        return None
    found = None
    for part in op_name.split("/"):
        part = _unwrap(part)
        if part in STEP_SCOPES:
            found = part
    return found


def scopes_in_hlo(hlo_text: str) -> Set[str]:
    """The stages that some instruction of an HLO module's text carries."""
    return {s for s in map(scope_of, _OP_NAME.findall(hlo_text)) if s is not None}
