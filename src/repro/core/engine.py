"""Pluggable layer-1 engine registry for the FedGAT model.

The paper defines a family of interchangeable approximations for the first
GAT layer (the only layer that needs raw cross-client features): Matrix
FedGAT (§4), Vector FedGAT (Appendix F), the direct polynomial oracle, the
fused Pallas kernel, and the exact-GAT degenerate case. Each is an
:class:`Engine` subclass registered under a name:

    @register_engine("matrix")
    class MatrixEngine(Engine):
        ...

    engine = get_engine("matrix")(cfg)     # cfg: FedGATConfig
    pack = engine.precompute(key, h, nbr_idx, nbr_mask)
    x = engine.apply(params, pack, coeffs, h, nbr_idx, nbr_mask, concat=True)

Adding an engine is a one-file change: subclass :class:`Engine`, decorate
with :func:`register_engine`, and every call site — ``fedgat_forward``,
``make_pack``, the :class:`~repro.core.fedgat_model.FedGAT` facade, both
federated trainer backends — picks it up by name.
"""
from __future__ import annotations

from typing import Any, Callable, ClassVar, Dict, List, Optional, Type

import jax

from repro.core.fedgat_matrix import fedgat_layer_matrix, precompute_pack
from repro.core.fedgat_vector import fedgat_layer_vector, precompute_vector_pack
from repro.core.gat import gat_layer_nbr
from repro.core.nbr_gather import NbrTable
from repro.core.poly_attention import poly_gat_layer

Array = jax.Array

_ENGINES: Dict[str, Type["Engine"]] = {}


def register_engine(name: str) -> Callable[[Type["Engine"]], Type["Engine"]]:
    """Class decorator registering an :class:`Engine` under ``name``."""

    def decorator(cls: Type["Engine"]) -> Type["Engine"]:
        if name in _ENGINES:
            raise ValueError(f"engine {name!r} already registered ({_ENGINES[name]!r})")
        cls.name = name
        _ENGINES[name] = cls
        return cls

    return decorator


def unregister_engine(name: str) -> None:
    """Remove an engine from the registry (no-op if absent). Intended for
    tests and plugin teardown."""
    _ENGINES.pop(name, None)


def registered_engines() -> List[str]:
    """Names of all registered engines, sorted."""
    return sorted(_ENGINES)


class UnknownEngineError(KeyError, ValueError):
    """Unknown engine name. Subclasses both KeyError (registry contract)
    and ValueError (the pre-registry ``fedgat_forward`` contract)."""

    def __str__(self):  # KeyError.__str__ would repr() the message
        return self.args[0] if self.args else ""


def get_engine(name: str) -> Type["Engine"]:
    """Resolve an engine class by name; the error lists what is available."""
    try:
        return _ENGINES[name]
    except KeyError:
        raise UnknownEngineError(
            f"unknown engine {name!r}: registered engines are {registered_engines()}"
        ) from None


class Engine:
    """Layer-1 engine interface.

    An engine is constructed from a ``FedGATConfig`` (which carries the
    series basis/domain/degree and the obfuscation constant ``r``) and
    provides the two halves of the paper's protocol:

    * :meth:`precompute` — the one-shot pre-training communication round
      (server side). Returns the engine's pack payload, or ``None`` for
      engines that need no pack.
    * :meth:`apply` — the client-side layer-1 update from the pack (or
      directly from features, for pack-free engines).
    """

    name: ClassVar[str] = "?"
    needs_pack: ClassVar[bool] = False     # precompute() returns a payload
    needs_coeffs: ClassVar[bool] = True    # apply() consumes series coeffs
    # Pre-training communication accounting model ("matrix" | "vector" |
    # "none"; see federated/comm.py). Default charges the Matrix FedGAT
    # rate (Theorem 1) — right for engines that simulate the matrix
    # protocol; custom engines should declare their own.
    comm_cost_model: ClassVar[str] = "matrix"

    def __init__(self, cfg):
        self.cfg = cfg

    def precompute(
        self, key: Array, h: Array, nbr_idx: Array, nbr_mask: Array
    ) -> Optional[Any]:
        return None

    def apply(
        self,
        params: Any,
        pack: Optional[Any],
        coeffs: Optional[Array],
        h: Array,
        nbr_idx: Array,
        nbr_mask: Array,
        *,
        concat: bool = True,
        table: Optional[NbrTable] = None,
    ) -> Array:
        """Layer 1 under the edge-visibility mask ``nbr_mask``. ``table``
        (the graph's lists) lets an engine that gathers neighbour scores
        use :func:`~repro.core.nbr_gather.nbr_gather`'s gather backward:
        ``kernel``, ``direct`` and ``exact`` do; ``matrix`` and ``vector``
        read their pack and ignore it (the exact layers after layer 1
        still take it, see ``layered_forward``)."""
        raise NotImplementedError


@register_engine("matrix")
class MatrixEngine(Engine):
    """Matrix FedGAT (paper §4, Algorithm 1/2): projector-matrix pack."""

    needs_pack = True

    def precompute(self, key, h, nbr_idx, nbr_mask):
        return precompute_pack(key, h, nbr_idx, nbr_mask, self.cfg.r)

    def apply(self, params, pack, coeffs, h, nbr_idx, nbr_mask, *, concat=True, table=None):
        return fedgat_layer_matrix(
            params, pack, h, coeffs,
            basis=self.cfg.basis, domain=self.cfg.domain, concat=concat,
        )


@register_engine("vector")
class VectorEngine(Engine):
    """Vector FedGAT (paper Appendix F): disjoint-support vector pack."""

    needs_pack = True
    comm_cost_model = "vector"

    def precompute(self, key, h, nbr_idx, nbr_mask):
        return precompute_vector_pack(key, h, nbr_idx, nbr_mask)

    def apply(self, params, pack, coeffs, h, nbr_idx, nbr_mask, *, concat=True, table=None):
        return fedgat_layer_vector(
            params, pack, h, coeffs,
            basis=self.cfg.basis, domain=self.cfg.domain, concat=concat,
        )


@register_engine("direct")
class DirectEngine(Engine):
    """The mathematical oracle: same series, per-edge, no pack."""

    def apply(self, params, pack, coeffs, h, nbr_idx, nbr_mask, *, concat=True, table=None):
        return poly_gat_layer(
            params, coeffs, h, nbr_idx, nbr_mask,
            basis=self.cfg.basis, domain=self.cfg.domain, concat=concat,
            table=table,
        )


@register_engine("kernel")
class KernelEngine(Engine):
    """Fused Pallas polynomial-attention kernel (see repro/kernels)."""

    def __init__(self, cfg):
        super().__init__(cfg)
        # Imported on construction, so a missing Pallas stack fails here
        # and never runs some other engine in the kernel's place.
        from repro.kernels import ops

        self._ops = ops

    def apply(self, params, pack, coeffs, h, nbr_idx, nbr_mask, *, concat=True, table=None):
        return self._ops.cheb_attn_layer(
            params, coeffs, h, nbr_idx, nbr_mask,
            basis=self.cfg.basis, domain=self.cfg.domain, concat=concat,
            table=table,
        )


@register_engine("exact")
class ExactEngine(Engine):
    """Plain GAT layer (degenerate engine, for baselines like DistGAT)."""

    needs_coeffs = False
    comm_cost_model = "none"  # no pack is communicated

    def apply(self, params, pack, coeffs, h, nbr_idx, nbr_mask, *, concat=True, table=None):
        return gat_layer_nbr(params, h, nbr_idx, nbr_mask, concat=concat, table=table)
