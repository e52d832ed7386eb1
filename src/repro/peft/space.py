"""ParamSpace — declarative trainable/shippable parameter subspaces.

The repo grew three disjoint mechanisms for "train and ship less than the
full model": FFDAPT frozen windows (``frozen=`` kwargs threaded through the
round engines), ``Compressed`` delta codecs, and — new here — low-rank
adapters.  :class:`ParamSpace` unifies them behind one contract:

``full``
    Today's FedAvg rounds, untouched.  ``inject`` is a no-op, the shippable
    tree is the whole model.
``frozen_window``
    FFDAPT re-expressed: the trainable subspace is the unfrozen layer
    window.  The engines keep running the exact pre-refactor masked/static
    step programs (bitwise identity is pinned in tests); what the space adds
    is honest *accounting* — :func:`frozen_shippable_template` prices a
    client's upload at only its unfrozen rows.
``lora(rank, targets)``
    Low-rank deltas ΔW = (alpha/r)·A@B injected next to the attention/MLP/
    Mamba projections named in :data:`repro.models.blocks.PEFT_TARGETS`.  The A/B
    factor tree (the *bank*) becomes the params tree the federated
    strategies see: aggregation, compression, upload/download accounting and
    the cohort-scan carry all run in subspace coordinates, so comm and
    peak-live shrink to O(bank) with no strategy changes.
``adapter(bottleneck, targets)``
    Linear residual output adapters: W' = W·(I + D@U), i.e. ΔW = W@(D@U).
    Deliberately linear (no nonlinearity between D and U) so the serve-time
    merge ``W + ΔW`` is exact, not an approximation.

Both low-rank kinds zero-init the second factor, so ``merge(base, inject
(base)) == base`` bitwise — a freshly injected run starts from the base
model exactly.

Training applies the bank unmerged (``unmerged``): each adapted projection
computes ``x W + s (x A) B`` (``repro.nn.layers.project``), so ΔW is never
rounded into a low-precision W and no full weight is rebuilt per step.
``merge`` is the serving view.

The bank is a plain nested-dict pytree mirroring the base tree's paths,
with each adapted leaf replaced by ``{"a": A, "b": B}`` — it checkpoints,
fingerprints, and aggregates like any params tree.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.blocks import PEFT_GROUPS, PEFT_TARGETS

_FNV32 = (2166136261, 16777619)


def _name_hash(name: str) -> int:
    """FNV-1a 31-bit — same scheme as ParamCtx._key_for (python ``hash()``
    is salted per-process; checkpoint determinism needs a stable one)."""
    h, mul = _FNV32
    for ch in name.encode():
        h = ((h ^ ch) * mul) & 0xFFFFFFFF
    return h & 0x7FFFFFFF


def _path_parts(path) -> Tuple[str, ...]:
    out = []
    for q in path:
        out.append(str(getattr(q, "key", getattr(q, "idx", q))))
    return tuple(out)


def _bank_set(bank: dict, parts: Tuple[str, ...], value: Any) -> None:
    node = bank
    for p in parts[:-1]:
        node = node.setdefault(p, {})
    node[parts[-1]] = value


def _bank_get(bank: dict, parts: Tuple[str, ...]) -> Any:
    node = bank
    for p in parts:
        node = node[p]
    return node


@dataclasses.dataclass(frozen=True)
class ParamSpace:
    """Declarative description of the trainable/shippable subspace.

    Hashable (frozen dataclass, tuple targets) so it can key the engines'
    compiled-step caches and the telemetry cost cache directly.
    """

    kind: str = "full"
    rank: int = 0
    alpha: float = 0.0
    targets: Tuple[str, ...] = ("attn", "mlp")

    def __post_init__(self):
        if self.kind not in ("full", "frozen_window", "lora", "adapter"):
            raise ValueError(f"unknown param space kind {self.kind!r}")
        if self.low_rank and self.rank < 1:
            raise ValueError(f"{self.kind} needs rank >= 1, got {self.rank}")
        for t in self.targets:
            if t not in PEFT_TARGETS:
                raise ValueError(
                    f"unknown PEFT target {t!r}; known: {sorted(PEFT_TARGETS)}")

    # -- identity ----------------------------------------------------------

    @property
    def low_rank(self) -> bool:
        return self.kind in ("lora", "adapter")

    @property
    def scale(self) -> float:
        """LoRA merge scale alpha/r (1.0 when alpha unset, and for adapters)."""
        if self.kind != "lora":
            return 1.0
        return (self.alpha or float(self.rank)) / float(self.rank)

    def step_key(self, frozen) -> Any:
        """Compiled-step cache key component.  full/frozen_window return the
        freeze mask verbatim so they share cache entries (and programs) with
        pre-ParamSpace sessions; low-rank spaces key on their geometry."""
        if not self.low_rank:
            return frozen
        return (self.kind, self.rank, float(self.alpha), self.targets)

    def to_json(self) -> dict:
        if not self.low_rank:
            return {"kind": self.kind}
        return {"kind": self.kind, "rank": self.rank,
                "alpha": float(self.alpha), "targets": list(self.targets)}

    @classmethod
    def from_json(cls, d: Optional[dict]) -> Optional["ParamSpace"]:
        if d is None:
            return None
        return cls(kind=d["kind"], rank=int(d.get("rank", 0)),
                   alpha=float(d.get("alpha", 0.0)),
                   targets=tuple(d.get("targets", ("attn", "mlp"))))

    # -- targeting ---------------------------------------------------------

    def _target_split(self, parts: Tuple[str, ...]) -> Optional[Tuple[int, int]]:
        """(n_in_dims, n_out_dims) when this leaf is adapted, else None."""
        if not self.low_rank or len(parts) < 2:
            return None
        name = parts[-1]
        for group in self.targets:
            if name in PEFT_TARGETS[group] and any(
                    c in parts[:-1] for c in PEFT_GROUPS[group]):
                return PEFT_TARGETS[group][name]
        return None

    def _factor_shapes(self, shape: Tuple[int, ...], split: Tuple[int, int]):
        """Leaf shape -> (stack, d_in, d_out, a_shape, b_shape)."""
        ni, no = split
        stack = shape[:len(shape) - ni - no]
        din = int(np.prod(shape[len(stack):len(stack) + ni]))
        dout = int(np.prod(shape[len(shape) - no:]))
        if self.kind == "adapter":
            # W' = W (I + D U): D maps output -> bottleneck, U back out.
            a_shape = stack + (dout, self.rank)
        else:
            a_shape = stack + (din, self.rank)
        b_shape = stack + (self.rank, dout)
        return stack, din, dout, a_shape, b_shape

    # -- bank construction / algebra --------------------------------------

    def inject(self, params: Any, key: Optional[jax.Array] = None) -> Any:
        """Build the trainable bank for ``params`` (empty dict for non-low-rank
        spaces).  A factors are normal-init with deterministic per-leaf keys
        (FNV hash of the leaf path folded into ``key``); B factors are zeros,
        so the injected delta starts at exactly 0."""
        if not self.low_rank:
            return {}
        if key is None:
            key = jax.random.PRNGKey(0)
        leaves, _ = jax.tree_util.tree_flatten_with_path(params)
        bank: dict = {}
        n_hit = 0
        for path, leaf in leaves:
            parts = _path_parts(path)
            split = self._target_split(parts)
            if split is None:
                continue
            n_hit += 1
            _, din, dout, a_shape, b_shape = self._factor_shapes(leaf.shape, split)
            fan = dout if self.kind == "adapter" else din
            std = (1.0 / max(fan, 1)) ** 0.5
            k = jax.random.fold_in(key, _name_hash("/".join(parts)))
            a = std * jax.random.normal(k, a_shape, jnp.float32)
            b = jnp.zeros(b_shape, jnp.float32)
            _bank_set(bank, parts, {"a": a, "b": b})
        if not n_hit:
            raise ValueError(
                f"param space {self.kind}(targets={self.targets}) matched no "
                "leaves in this model — nothing would train")
        return bank

    def _delta(self, w: Any, ab: dict) -> Any:
        """Float32 ΔW for one adapted leaf, shaped like ``w``."""
        a = ab["a"].astype(jnp.float32)
        b = ab["b"].astype(jnp.float32)
        low = jnp.matmul(a, b)                       # stack + (din|dout, dout)
        if self.kind == "adapter":
            stack = w.shape[:low.ndim - 2]
            dout = low.shape[-1]
            din = int(np.prod(w.shape[len(stack):])) // dout
            w2 = w.reshape(stack + (din, dout)).astype(jnp.float32)
            low = jnp.matmul(w2, low)                # W @ (D U)
        else:
            low = low * self.scale
        return low.reshape(w.shape)

    def unmerged(self, base: Any, bank: Any) -> Any:
        """The base tree with each adapted leaf replaced by a
        ``repro.nn.layers.LowRank`` of the frozen weight and its factors,
        which the model's projections apply unmerged (the training view).
        Untargeted leaves pass through as the same array objects."""
        if not self.low_rank:
            return base
        from repro.nn.layers import LowRank

        def one(path, leaf):
            parts = _path_parts(path)
            split = self._target_split(parts)
            if split is None:
                return leaf
            ab = _bank_get(bank, parts)
            return LowRank(leaf, ab["a"], ab["b"], scale=self.scale,
                           kind=self.kind, n_in=split[0], n_out=split[1])

        return jax.tree_util.tree_map_with_path(one, base)

    def merge(self, base: Any, bank: Any) -> Any:
        """Fold the bank's deltas into the base tree (serve/eval view).

        Untargeted leaves pass through as the same array objects; targeted
        leaves accumulate in float32 and cast back to the leaf dtype, so a
        zero bank merges to the base bitwise."""
        if not self.low_rank:
            return base

        def one(path, leaf):
            parts = _path_parts(path)
            if self._target_split(parts) is None:
                return leaf
            ab = _bank_get(bank, parts)
            return (leaf.astype(jnp.float32) + self._delta(leaf, ab)
                    ).astype(leaf.dtype)

        return jax.tree_util.tree_map_with_path(one, base)

    def extract_delta(self, base: Any, bank: Any) -> Any:
        """Dense ΔW tree (zeros for untargeted leaves) — what ``merge`` adds.
        Diagnostic / comm-analysis view; the wire format stays the bank."""
        if not self.low_rank:
            return jax.tree.map(jnp.zeros_like, base)

        def one(path, leaf):
            parts = _path_parts(path)
            if self._target_split(parts) is None:
                return jnp.zeros_like(leaf)
            return self._delta(leaf, _bank_get(bank, parts)).astype(leaf.dtype)

        return jax.tree_util.tree_map_with_path(one, base)

    def train_mask(self, base: Any, frozen=None, cfg=None) -> Any:
        """0/1 float tree over ``base``: 1 where a base leaf (or row, for
        frozen windows) is trainable *in base coordinates*.  Low-rank spaces
        train no base leaf at all — their trainables live in the bank."""
        if self.low_rank:
            return jax.tree.map(lambda l: jnp.zeros(l.shape, jnp.float32), base)
        if self.kind == "frozen_window" and frozen is not None and cfg is not None:
            from repro.models.steps import _stack_masks
            masks = dict(_stack_masks(cfg, frozen))

            def one(path, leaf):
                top = _path_parts(path)[0]
                if top in masks:
                    keep = (1.0 - masks[top]).reshape(
                        (-1,) + (1,) * (leaf.ndim - 1))
                    return jnp.broadcast_to(keep, leaf.shape).astype(jnp.float32)
                return jnp.ones(leaf.shape, jnp.float32)

            return jax.tree_util.tree_map_with_path(one, base)
        return jax.tree.map(lambda l: jnp.ones(l.shape, jnp.float32), base)

    # -- accounting --------------------------------------------------------

    def shippable_tree(self, params: Any, *, bank: Any = None, frozen=None,
                       cfg=None) -> Any:
        """The tree a client actually ships, for byte accounting.  Low-rank:
        the bank.  frozen_window with an active mask: the unfrozen-row
        template.  Otherwise: the full tree."""
        if self.low_rank:
            return bank if bank is not None else params
        if (self.kind == "frozen_window" and frozen is not None
                and any(frozen) and cfg is not None):
            return frozen_shippable_template(cfg, params, frozen)
        return params

    def trainable_fraction(self, base: Any, *, bank: Any = None,
                           frozen=None) -> float:
        """Trainable params / base params — the analytic dW-FLOP discount
        (backward dW work scales with this fraction)."""
        total = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(base))
        if self.low_rank:
            live = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(bank))
            return live / max(total, 1)
        if self.kind == "frozen_window" and frozen is not None and frozen:
            return 1.0 - sum(frozen) / len(frozen)
        return 1.0


def frozen_shippable_template(cfg, params: Any, frozen: Sequence[bool]) -> Any:
    """ShapeDtypeStruct tree of what a frozen-window client ships: stacked
    top-level entries ("layers"; audio: "enc_layers"+"layers") sliced to
    their unfrozen rows, everything else full-shape.  Feeding this to
    ``strategy.upload_bytes`` prices dense, top-k and int8 uploads in the
    subspace — the strategies' byte formulas are tree-generic."""
    from repro.models.steps import _stack_masks
    kept = {k: int(len(m) - np.sum(np.asarray(m)))
            for k, m in _stack_masks(cfg, frozen)}

    def one(path, leaf):
        top = _path_parts(path)[0]
        shape = leaf.shape
        if top in kept and len(shape) >= 1:
            shape = (kept[top],) + tuple(shape[1:])
        return jax.ShapeDtypeStruct(shape, leaf.dtype)

    return jax.tree_util.tree_map_with_path(one, params)


# -- constructors -----------------------------------------------------------

def full() -> ParamSpace:
    return ParamSpace("full")


def frozen_window() -> ParamSpace:
    return ParamSpace("frozen_window")


def lora(rank: int, *, alpha: float = 0.0,
         targets: Sequence[str] = ("attn", "mlp")) -> ParamSpace:
    return ParamSpace("lora", rank=int(rank), alpha=float(alpha),
                      targets=tuple(targets))


def adapter(bottleneck: int, *,
            targets: Sequence[str] = ("attn", "mlp")) -> ParamSpace:
    return ParamSpace("adapter", rank=int(bottleneck), targets=tuple(targets))


def make_param_space(name: str, *, rank: int = 4, alpha: float = 0.0,
                     adapter_dim: int = 8,
                     targets: Sequence[str] = ("attn", "mlp")) -> ParamSpace:
    """Flag-shaped builder used by ``launch/train.py``."""
    if name == "full":
        return full()
    if name == "frozen_window":
        return frozen_window()
    if name == "lora":
        return lora(rank, alpha=alpha, targets=targets)
    if name == "adapter":
        return adapter(adapter_dim, targets=targets)
    raise ValueError(f"unknown param space {name!r}")
