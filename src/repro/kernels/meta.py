"""Kernel schedule contracts: what each Pallas kernel *declares* about
its zero-stall schedule, exposed where IR tracing cannot see it.

``jax.make_jaxpr`` over an ``ops.*`` entry point recovers the grid, the
BlockSpecs, and the kernel body of every emitted ``pallas_call`` — but
not the *intent*: which grid axis streams the contraction, whether the
kernel issues its own HBM→VMEM DMAs (the N-slot revolving buffer) or
leans on the Pallas pipeline's automatic double buffering, and how many
slots the schedule was built for.  Each kernel module registers a
:class:`ScheduleContract` here at import time and stamps its
``pallas_call`` name via :func:`kernel_name`, so the static verifier
(:mod:`repro.analyze.kernel_lint`) can match an IR-derived timeline
against the declared schedule instead of guessing from string patterns.

The name is the join point: ``pallas_call`` equations carry their
kernel name in the IR, so ``contract_for(name)`` is the only lookup the
verifier needs.
"""

from __future__ import annotations

import dataclasses
import re

from jax.experimental.pallas import tpu as pltpu

from repro.core.cyclemodel import VMEM_LIMIT_BYTES

__all__ = ["ScheduleContract", "register_family", "kernel_name",
           "contract_for", "registered_families", "compiler_params"]


def compiler_params(*dimension_semantics: str) -> pltpu.CompilerParams:
    """Mosaic parameters shared by every kernel: the grid semantics
    plus the scoped VMEM limit the tuner's space budgets against
    (:data:`repro.core.cyclemodel.VMEM_LIMIT_BYTES`)."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


@dataclasses.dataclass(frozen=True)
class ScheduleContract:
    """Declared schedule of one kernel family.

    ``family``: the name prefix shared by every instantiation.
    ``grid_rank``: expected number of grid axes.
    ``managed_dma``: True when the kernel body issues explicit
    HBM→VMEM copies into an N-slot revolving buffer (the matmul
    families); False when operand movement is the Pallas pipeline's
    automatic BlockSpec double buffering (the attention families).
    ``sequential_axes``: ``"all"`` when every grid axis must be
    sequential (``"arbitrary"``) because DMA/accumulator state is
    carried across steps; ``"last"`` when only the innermost streaming
    axis must be.
    ``slots``/``grid_order``: filled per-instantiation by
    :func:`contract_for` from the kernel name (None on the family
    template).
    """

    family: str
    grid_rank: int
    managed_dma: bool
    sequential_axes: str = "all"
    slots: int | None = None
    grid_order: str | None = None


_REGISTRY: dict[str, ScheduleContract] = {}

# instantiation suffix: "_s{slots}" then optionally "_{grid_order}"
_SUFFIX = re.compile(r"^(?:_s(?P<slots>\d+))?(?:_(?P<order>[a-z]{3}))?$")


def register_family(family: str, *, grid_rank: int, managed_dma: bool,
                    sequential_axes: str = "all") -> ScheduleContract:
    """Declare one kernel family's schedule contract (import-time)."""
    if sequential_axes not in ("all", "last"):
        raise ValueError(f"sequential_axes must be 'all' or 'last', "
                         f"got {sequential_axes!r}")
    contract = ScheduleContract(family=family, grid_rank=grid_rank,
                                managed_dma=managed_dma,
                                sequential_axes=sequential_axes)
    _REGISTRY[family] = contract
    return contract


def kernel_name(family: str, *, slots: int | None = None,
                grid_order: str | None = None) -> str:
    """Build the canonical (parseable) ``pallas_call`` name."""
    if family not in _REGISTRY:
        raise ValueError(f"unregistered kernel family: {family!r}")
    name = family
    if slots is not None:
        name += f"_s{int(slots)}"
    if grid_order is not None:
        name += f"_{grid_order}"
    return name


def contract_for(name: str) -> ScheduleContract | None:
    """Resolve a ``pallas_call`` name to its instantiated contract.

    Longest-prefix match over the registered families, then the
    ``_s{slots}_{order}`` suffix is parsed back into the contract.
    Returns None for kernels this repo does not govern.
    """
    for family in sorted(_REGISTRY, key=len, reverse=True):
        if name == family or name.startswith(family + "_"):
            m = _SUFFIX.match(name[len(family):])
            if m is None:
                continue
            slots = m.group("slots")
            return dataclasses.replace(
                _REGISTRY[family],
                slots=int(slots) if slots is not None else None,
                grid_order=m.group("order"))
    return None


def registered_families() -> tuple[str, ...]:
    """Registered family prefixes (sorted, for reporting)."""
    return tuple(sorted(_REGISTRY))
