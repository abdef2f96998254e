"""SailLinear: quantized-weight matmul dispatch, the framework's integration
point for the paper's technique.

Every weight matmul in the model goes through ``mm(x, w)``:
  * training / unquantized serving: ``w`` is a plain array -> jnp.dot;
  * SAIL serving: ``w`` is a ``QTensor`` (packed intN + group scales +
    codebook LUT) -> the LUT-dequant matmul (Pallas kernel on TPU, its
    same-semantics jnp form when lowering on CPU / inside the dry-run).

``quantize_params`` converts a trained parameter tree into the SAIL serving
format (the offline step the ``ql`` instruction field selects at runtime);
embedding tables and 1-D params (norms, biases) stay in f32, mirroring the
paper's mixed-precision outlier handling.

Mixed precision: the paper's whole point is supporting *arbitrary* ql with
minimal overhead ("optimal bit precision varies across models and layers",
Sec. I).  ``QuantPolicy`` therefore resolves bits per parameter path:

  * ``rules``       — explicit (regex, bits) overrides, first match wins;
  * ``allocation``  — a :class:`BitAllocation` (typically produced by the
    sensitivity-driven allocator in ``repro.core.sensitivity``) mapping a
    path to a scalar or to a per-layer tuple of bits;
  * ``bits``        — the uniform fallback.

Scan-stacked layers can only carry one static ``bits`` per stack, so a
per-layer tuple on a ``blocks`` leaf splits the stack into maximal
uniform-bits *segments*: ``params["blocks"]`` becomes a list of stacked
trees the model applies back-to-back (``repro.models.lm`` scans each
segment; single-segment trees keep today's exact semantics).

Activation precision: the ``lutmm`` instruction parameterizes *both* the
weight (``ql``) and the activation precision per call, so the policy also
resolves ``abits`` per path (``act_rules`` / ``allocation.act_per_path`` /
``act_bits``).  A quantized leaf carries its allocated ``abits`` as static
metadata and ``mm``/``einsum_q`` run the *real* integer path: activations
are quantized per token (``quantize_activations``) and the integer codes
plus per-token scale enter the LUT-GEMV kernel directly (``abits=None``
keeps today's f32-activation semantics).  Fake-quant survives only as the
calibration probe (``ActQuantWeight``).  Per-layer ``abits`` tuples
segment the scan stack exactly like weight bits do — a segment is maximal
in the *joint* (wbits, abits) assignment.
"""
from __future__ import annotations

import dataclasses
import re
from functools import partial
from typing import Any, Dict, List, Mapping, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.quant import (SUPPORTED_ABITS, SUPPORTED_BITS, QTensor,
                              _uniform_codebook, nf_codebook, quantize,
                              quantize_activations)

__all__ = [
    "ActQuantWeight", "BitAllocation", "QuantPolicy", "QTensor",
    "StackedQTensor", "act_fake_quant", "dequantize_any", "einsum_q", "mm",
    "nf_codebook", "quantize_params", "ragged_q", "set_backend",
]

# Module-level backend switch: "jnp" (XLA path — used under pjit / dry-run)
# or "pallas" (the kernel: compiled on a TPU, interpreted elsewhere).
_BACKEND = "jnp"


def set_backend(backend: str) -> None:
    global _BACKEND
    assert backend in ("jnp", "pallas")
    if backend != _BACKEND:
        # the switch is read at trace time and is not part of any jit
        # cache key: a program traced under the old backend would keep
        # serving it (the engine's jitted decode step among them)
        jax.clear_caches()
    _BACKEND = backend


def act_fake_quant(x: jax.Array, abits: int) -> jax.Array:
    """Per-token activation quantize->dequantize at ``abits`` — the error
    a SAIL matmul serving ``lutmm(..., abits)`` would see on its inputs.
    Works for any leading shape (the last axis is the token's feature
    vector)."""
    xq, xs = quantize_activations(x, abits)
    return (xq.astype(jnp.float32) * xs).astype(x.dtype)


def _apply_act_quant(x: jax.Array, w: Any):
    """Unwrap an ``ActQuantWeight`` probe (gate-blended fake-quant, so one
    scan pass can probe a single layer of a stack).

    This is the *only* place fake-quant touches activations: quantized
    leaves carrying ``abits`` run the real integer path inside
    ``mm``/``einsum_q`` instead.  Returns the (possibly probed)
    activations and the unwrapped weight."""
    if isinstance(w, ActQuantWeight):
        fq = act_fake_quant(x, w.abits)
        x = x + w.gate.astype(x.dtype) * (fq - x)
        w = w.w
    return x, w


def mm(x: jax.Array, w: Any) -> jax.Array:
    """x [..., K] @ w [K, N] with QTensor dispatch.

    Its ops carry the ``sail_mm`` scope in their names, so a profiler
    trace can sum the matmuls' device time (dequant and dot)."""
    with jax.named_scope("sail_mm"):
        x, w = _apply_act_quant(x, w)
        if isinstance(w, StackedQTensor) and w.packed.ndim == 2:
            # a scan-sliced layer: reinterpret as a plain QTensor
            w = QTensor(packed=w.packed, scales=w.scales,
                        codebook=w.codebook, bits=w.bits,
                        group_size=w.group_size, k=w.k, abits=w.abits)
        if isinstance(w, QTensor):
            from repro.kernels.lut_gemv.ops import lut_matmul
            lead = x.shape[:-1]
            x2 = x.reshape(-1, x.shape[-1])
            y = lut_matmul(x2, w, out_dtype=x.dtype if x.dtype != jnp.int32
                           else jnp.float32, backend=_BACKEND)
            return y.reshape(*lead, w.n)
        return x @ w


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ActQuantWeight:
    """Probe wrapper: a plain weight whose *matmul inputs* are quantized.

    Used by ``repro.core.sensitivity.activation_sensitivity`` to measure
    the end-to-end error of quantizing one unit's activations at a
    candidate ``abits`` while everything else stays at the baseline.  The
    ``gate`` array (scalar, or [L] for scan-stacked weights — scan slices
    both fields in lockstep) turns the fake-quant on per layer, so one
    compiled forward probes every layer of a stack."""
    w: jax.Array
    gate: jax.Array
    abits: int = dataclasses.field(metadata=dict(static=True))


# Bits for one path: a scalar, or one entry per scan-stacked layer.
BitsSpec = Union[int, Tuple[int, ...]]


def _bits_spec_to_json(per_path: Mapping[str, BitsSpec]) -> Dict[str, Any]:
    return {p: (list(map(int, b)) if isinstance(b, (tuple, list))
                else int(b))
            for p, b in per_path.items()}


def _bits_spec_from_json(spec: Mapping[str, Any]) -> Dict[str, BitsSpec]:
    return {p: (tuple(int(x) for x in b) if isinstance(b, (list, tuple))
                else int(b))
            for p, b in spec.items()}


@dataclasses.dataclass(frozen=True)
class BitAllocation:
    """Per-path bit-width assignment (the allocator's output).

    ``per_path`` maps ``jax.tree_util.keystr`` paths to a scalar weight
    bits or, for scan-stacked ``blocks`` leaves, a per-layer tuple.
    ``act_per_path`` carries the jointly allocated activation precision
    the same way (absent paths keep the policy's ``act_bits`` fallback).
    JSON-safe via ``to_spec``/``from_spec`` so checkpoints can embed the
    allocation; the legacy flat weight-only spec format still parses.
    """
    per_path: Mapping[str, BitsSpec]
    act_per_path: Mapping[str, BitsSpec] = dataclasses.field(
        default_factory=dict)

    def lookup(self, path: str) -> Optional[BitsSpec]:
        return self.per_path.get(path)

    def lookup_act(self, path: str) -> Optional[BitsSpec]:
        return self.act_per_path.get(path)

    def to_spec(self) -> Dict[str, Any]:
        if not self.act_per_path:
            return _bits_spec_to_json(self.per_path)   # legacy flat format
        return {"weights": _bits_spec_to_json(self.per_path),
                "activations": _bits_spec_to_json(self.act_per_path)}

    @staticmethod
    def from_spec(spec: Mapping[str, Any]) -> "BitAllocation":
        if "weights" in spec and "activations" in spec:
            return BitAllocation(
                per_path=_bits_spec_from_json(spec["weights"]),
                act_per_path=_bits_spec_from_json(spec["activations"]))
        return BitAllocation(per_path=_bits_spec_from_json(spec))


@dataclasses.dataclass(frozen=True)
class QuantPolicy:
    bits: int = 4                  # uniform fallback precision
    group_size: int = 128
    min_size: int = 65536          # don't quantize small tensors
    skip_embed: bool = True        # gathers can't stream through LUT-GEMV
    # None | array (single-precision policies only) | callable bits->array
    # (e.g. ``nf_codebook`` — mixed policies need a per-bits codebook)
    codebook: Optional[Any] = None
    rules: Tuple[Tuple[str, int], ...] = ()     # (regex, bits), first match
    allocation: Optional[BitAllocation] = None  # sensitivity allocator output
    # activation precision: uniform fallback (None = f32 activations) and
    # explicit per-path overrides, resolved like the weight side
    act_bits: Optional[int] = None
    act_rules: Tuple[Tuple[str, int], ...] = ()

    def bits_for(self, path: str) -> BitsSpec:
        """Resolve the bit width for one parameter path.

        Explicit rules override the automatic allocation, which overrides
        the uniform fallback."""
        for pat, b in self.rules:
            if re.search(pat, path):
                return _check_bits(int(b))
        if self.allocation is not None:
            got = self.allocation.lookup(path)
            if got is not None:
                return got
        return self.bits

    def abits_for(self, path: str) -> Optional[BitsSpec]:
        """Resolve the activation precision for one parameter path
        (``None`` = keep f32 activations for this matmul).  Same
        precedence as the weight side: act_rules > allocation >
        act_bits."""
        for pat, b in self.act_rules:
            if re.search(pat, path):
                return _check_abits(int(b))
        if self.allocation is not None:
            got = self.allocation.lookup_act(path)
            if got is not None:
                return got
        return self.act_bits

    def codebook_for(self, bits: int) -> Optional[jax.Array]:
        if self.codebook is None:
            return None
        if callable(self.codebook):
            return self.codebook(bits)
        if self.codebook.shape[-1] != (1 << bits):
            raise ValueError(
                f"explicit codebook has {self.codebook.shape[-1]} entries "
                f"but a leaf resolved to {bits} bits (2**{bits} needed) — "
                "mixed policies need a callable codebook factory")
        return self.codebook

    def is_mixed(self) -> bool:
        return (bool(self.rules) or bool(self.act_rules)
                or self.allocation is not None)

    def to_spec(self) -> Dict[str, Any]:
        """JSON-safe description (stored in checkpoint manifests)."""
        cb = self.codebook
        if cb is not None:
            if not callable(cb):
                raise ValueError(
                    "explicit codebook arrays are not spec-serializable; "
                    "use a named factory (nf_codebook) or None")
            if getattr(cb, "__name__", "") != "nf_codebook":
                raise ValueError(f"unknown codebook factory {cb!r}")
            cb = "nf"
        return {"bits": int(self.bits), "group_size": int(self.group_size),
                "min_size": int(self.min_size),
                "skip_embed": bool(self.skip_embed), "codebook": cb,
                "rules": [[p, int(b)] for p, b in self.rules],
                "allocation": (self.allocation.to_spec()
                               if self.allocation is not None else None),
                "act_bits": (int(self.act_bits)
                             if self.act_bits is not None else None),
                "act_rules": [[p, int(b)] for p, b in self.act_rules]}

    @staticmethod
    def from_spec(spec: Mapping[str, Any]) -> "QuantPolicy":
        cb = spec.get("codebook")
        if cb == "nf":
            cb = nf_codebook
        elif cb is not None:
            raise ValueError(f"unknown codebook spec {cb!r}")
        alloc = spec.get("allocation")
        act_bits = spec.get("act_bits")
        return QuantPolicy(
            bits=int(spec.get("bits", 4)),
            group_size=int(spec.get("group_size", 128)),
            min_size=int(spec.get("min_size", 65536)),
            skip_embed=bool(spec.get("skip_embed", True)),
            codebook=cb,
            rules=tuple((p, int(b)) for p, b in spec.get("rules", ())),
            allocation=(BitAllocation.from_spec(alloc)
                        if alloc else None),
            act_bits=int(act_bits) if act_bits is not None else None,
            act_rules=tuple((p, int(b))
                            for p, b in spec.get("act_rules", ())))


def _check_bits(b: int) -> int:
    if b not in SUPPORTED_BITS:
        raise ValueError(f"bits must be one of {SUPPORTED_BITS}, got {b}")
    return b


def _check_abits(b: Optional[int]) -> Optional[int]:
    if b is not None and b not in SUPPORTED_ABITS:
        raise ValueError(
            f"activation bits must be one of {SUPPORTED_ABITS} or None, "
            f"got {b}")
    return b


def _should_quantize(path: str, w, policy: QuantPolicy) -> bool:
    if not hasattr(w, "ndim") or w.ndim != 2 or "router" in path:
        return False
    if w.size < policy.min_size:
        return False
    if policy.skip_embed and ("embed" in path):
        return False
    if w.shape[0] % policy.group_size != 0:
        return False
    return True


def _should_quantize_stacked(path: str, w, policy: QuantPolicy) -> bool:
    """Scan-stacked [L, K, N] / MoE [L, E, K, N] weights.  An expert
    router stays float32: it picks which experts run, and deployments
    keep it unrounded."""
    return (hasattr(w, "ndim") and w.ndim >= 3
            and "embed" not in path and "router" not in path
            and w.shape[-2] % policy.group_size == 0
            and w.shape[-2] * w.shape[-1] >= policy.min_size)


def _scalar_bits(spec: BitsSpec, path: str, offset: int,
                 seg_len: Optional[int], check=_check_bits):
    """Resolve a BitsSpec to the single static bits of one leaf/segment."""
    if spec is None:
        return None
    if isinstance(spec, (tuple, list)):
        if seg_len is None:
            raise ValueError(
                f"per-layer bits on non-stacked leaf {path}: {spec}")
        window = set(spec[offset:offset + seg_len])
        if len(window) != 1:
            raise ValueError(
                f"heterogeneous bits {spec} for {path} require a top-level "
                "'blocks' stack (segmentation); got an unsplittable tree")
        return check(None if spec[offset] is None else int(spec[offset]))
    return check(int(spec))


@partial(jax.jit, donate_argnums=(0, 1))
def _put_slice(packed, scales, i, p, sc):
    return packed.at[i].set(p), scales.at[i].set(sc)


def _quantize_stacked(w, bits: int, policy: QuantPolicy,
                      abits: Optional[int] = None) -> "StackedQTensor":
    """Quantize a stacked weight one ``[K, N]`` slice at a time.

    Each slice moves to the device (from the host, for a NumPy leaf) and
    is rounded in its own compiled call, so neither the whole float32
    stack nor its rounding temporaries are ever on the device at once;
    under a trace (``jax.eval_shape``) the slices are mapped instead.
    The codebook is tiled along the first leading dim so the whole
    StackedQTensor can ride through ``lax.scan`` as an xs pytree."""
    from repro.core.quant import quantize_slice
    lead = w.shape[:-2]
    k, n = w.shape[-2:]
    g = policy.group_size
    cb = policy.codebook_for(bits)
    codebook = (_uniform_codebook(bits) if cb is None else cb).astype(
        jnp.float32)
    flat = w.reshape((-1, k, n))
    one = lambda a: quantize_slice(a, codebook, g, cb is None)  # noqa: E731
    if isinstance(w, jax.core.Tracer):
        packed, scales = jax.vmap(one)(flat)
    else:
        p0, s0 = jax.eval_shape(one, jax.ShapeDtypeStruct((k, n), w.dtype))
        packed = jnp.zeros((flat.shape[0],) + p0.shape, p0.dtype)
        scales = jnp.zeros((flat.shape[0],) + s0.shape, s0.dtype)
        for i in range(flat.shape[0]):
            packed, scales = _put_slice(
                packed, scales, i, *one(flat[i]))
    packed = packed.reshape(lead + packed.shape[1:])
    scales = scales.reshape(lead + scales.shape[1:])
    return StackedQTensor(
        packed=packed, scales=scales,
        codebook=jnp.tile(codebook[None], (lead[0], 1)),
        bits=bits, group_size=g, k=k, abits=abits)


def _quantize_tree(params, policy: QuantPolicy, offset: int = 0):
    """Quantize one tree whose resolved bits are uniform per leaf.

    ``offset`` is the absolute layer index of stacked leaves' first slice
    (nonzero when quantizing a blocks segment)."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    before = after = 0
    out = []
    for path, w in flat:
        pstr = jax.tree_util.keystr(path)
        before += w.size * w.dtype.itemsize
        if _should_quantize(pstr, w, policy):
            b = _scalar_bits(policy.bits_for(pstr), pstr, 0, None)
            ab = _scalar_bits(policy.abits_for(pstr), pstr, 0, None,
                              check=_check_abits)
            qt = quantize(w, b, policy.group_size,
                          codebook=policy.codebook_for(b))
            if ab is not None:
                qt = dataclasses.replace(qt, abits=ab)
            after += qt.nbytes()
            out.append(qt)
        elif _should_quantize_stacked(pstr, w, policy):
            b = _scalar_bits(policy.bits_for(pstr), pstr, offset,
                             w.shape[0])
            ab = _scalar_bits(policy.abits_for(pstr), pstr, offset,
                              w.shape[0], check=_check_abits)
            stacked = _quantize_stacked(w, b, policy, abits=ab)
            after += stacked.packed.size * 4 + stacked.scales.size * 4
            out.append(stacked)
        else:
            after += w.size * w.dtype.itemsize
            # a host leaf goes to the device once, here, and not with
            # every call of a step that takes the tree
            out.append(jnp.asarray(w))
    return jax.tree_util.tree_unflatten(treedef, out), before, after


def _segment_bounds(params, policy: QuantPolicy) -> Optional[List[int]]:
    """Layer cut points implied by per-layer bit specs on blocks leaves.

    Both the weight and the activation allocation segment the stack: a
    segment is maximal in the joint (wbits, abits) assignment, since a
    scan body can only carry one static precision pair per leaf.  Returns
    None when no segmentation is needed (no per-layer spec, or all
    per-layer specs constant)."""
    if not (isinstance(params, dict) and "blocks" in params
            and not isinstance(params["blocks"], (list, tuple))):
        return None
    flat = jax.tree_util.tree_flatten_with_path(
        {"blocks": params["blocks"]})[0]
    n_layers = None
    per_layer: List[Tuple[int, ...]] = []
    for path, w in flat:
        pstr = jax.tree_util.keystr(path)
        if not (_should_quantize(pstr, w, policy)
                or _should_quantize_stacked(pstr, w, policy)):
            continue
        for spec in (policy.bits_for(pstr), policy.abits_for(pstr)):
            if not isinstance(spec, (tuple, list)):
                continue
            if w.ndim < 3:
                raise ValueError(
                    f"per-layer bits on non-stacked leaf {pstr}")
            if len(spec) != w.shape[0]:
                raise ValueError(
                    f"allocation for {pstr} has {len(spec)} entries, stack "
                    f"has {w.shape[0]} layers")
            if n_layers is None:
                n_layers = w.shape[0]
            per_layer.append(tuple(spec))
    if not per_layer:
        return None
    cuts = [0]
    for layer in range(1, n_layers):
        if any(s[layer] != s[layer - 1] for s in per_layer):
            cuts.append(layer)
    cuts.append(n_layers)
    return cuts if len(cuts) > 2 else None


def quantize_params(params, policy: QuantPolicy = QuantPolicy()):
    """Convert a parameter tree to the SAIL serving format.

    Stacked weights — scan-stacked layers [L, K, N] and MoE experts
    [L, E, K, N] — are quantized one [K, N] slice at a time, a host
    (NumPy) leaf moving to the device slice by slice; the router stays
    float32.
    Bits are resolved per path (``policy.bits_for``); a per-layer tuple on
    a ``blocks`` leaf splits the stack into uniform-bits segments and the
    returned tree carries ``params["blocks"]`` as a list of stacked trees
    (see module docstring).  Returns (quantized tree, bytes_before,
    bytes_after).
    """
    bounds = _segment_bounds(params, policy)
    if bounds is None:
        return _quantize_tree(params, policy)
    rest = {k: v for k, v in params.items() if k != "blocks"}
    out, before, after = _quantize_tree(rest, policy)
    segments = []
    for a, b in zip(bounds[:-1], bounds[1:]):
        sub = jax.tree_util.tree_map(lambda x: x[a:b], params["blocks"])
        qseg, sb, sa = _quantize_tree({"blocks": sub}, policy, offset=a)
        segments.append(qseg["blocks"])
        before += sb
        after += sa
    out = dict(out)
    out["blocks"] = segments
    return out, before, after


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class StackedQTensor:
    """QTensor stacked along a leading axis (scan layers / MoE experts)."""
    packed: jax.Array      # [E, (K//G)*wpg, N]
    scales: jax.Array      # [E, K//G, N]
    codebook: jax.Array
    bits: int = dataclasses.field(metadata=dict(static=True))
    group_size: int = dataclasses.field(metadata=dict(static=True))
    k: int = dataclasses.field(metadata=dict(static=True))
    abits: Optional[int] = dataclasses.field(
        default=None, metadata=dict(static=True))

    def __getitem__(self, i):
        cb = self.codebook if self.codebook.ndim == 1 else self.codebook[i]
        return QTensor(packed=self.packed[i], scales=self.scales[i],
                       codebook=cb, bits=self.bits,
                       group_size=self.group_size, k=self.k,
                       abits=self.abits)

    @property
    def n(self):
        return self.packed.shape[-1]

    @property
    def shape(self):
        """Logical (unquantized) weight shape."""
        lead = self.packed.shape[:-2]
        return lead + (self.k, self.packed.shape[-1])


def dequantize_any(w):
    """Array | QTensor | StackedQTensor -> f32 array (oracle path)."""
    from repro.core.quant import dequantize, unpack_grouped
    if isinstance(w, QTensor):
        return dequantize(w)
    if isinstance(w, StackedQTensor):
        cb = w.codebook if w.codebook.ndim == 1 else w.codebook[0]
        lead = w.packed.shape[:-2]
        flat_p = w.packed.reshape((-1,) + w.packed.shape[-2:])
        flat_s = w.scales.reshape((-1,) + w.scales.shape[-2:])
        codes = jax.vmap(lambda p: unpack_grouped(
            p, w.bits, w.group_size, w.k))(flat_p)          # [S, K, N]
        n = codes.shape[-1]
        # look the codes up through a 2-D view: the TPU compiler turns a
        # small table's gather with 2-D indices into selects, but keeps
        # one with more index dims as an element-wise gather, far slower
        vals = cb[codes.reshape(-1, n)].reshape(
            codes.shape[0], w.k // w.group_size, w.group_size, n)
        out = vals * flat_s[:, :, None, :]
        return out.reshape(lead + (w.k, n))
    return w


def _einsum_scale_to_out(spec: str, x_shape, xs: jax.Array) -> Optional[jax.Array]:
    """Broadcast per-token activation scales to the einsum output.

    For ``spec`` where x's last subscript is the contracted axis and every
    other x subscript appears in the output (all MoE expert einsums),
    returns ``xs`` reshaped/transposed so ``einsum(xq, w) * xs_out`` equals
    the serve-path semantics.  Returns None when the spec doesn't fit
    (caller falls back to folding the scale into the input)."""
    lhs, out = spec.split("->")
    x_sub, _ = lhs.split(",")
    keep = x_sub[:-1]                       # non-contracted x subscripts
    if x_sub[-1] in out or any(c not in out for c in keep):
        return None
    xs_sq = xs[..., 0]                      # [*x_shape[:-1]]
    order = [keep.index(c) for c in out if c in keep]
    xs_t = jnp.transpose(xs_sq, order)
    dims, it = [], iter(xs_t.shape)
    for c in out:
        dims.append(next(it) if c in keep else 1)
    return xs_t.reshape(dims)


def ragged_q(x: jax.Array, w: Any, group_sizes: jax.Array) -> jax.Array:
    """Grouped matmul: rows of ``x [M, K]``, sorted by group, times their
    group's ``w [G, K, N]`` (``jax.lax.ragged_dot``), where w may be
    stacked-quantized.  A quantized stack is dequantized as
    :func:`einsum_q` does; with ``abits`` the per-token integer codes go
    in and the per-token scale is applied to the output."""
    x, w = _apply_act_quant(x, w)
    if isinstance(w, (QTensor, StackedQTensor)):
        wd = dequantize_any(w)
        if (w.abits is not None
                and jnp.issubdtype(x.dtype, jnp.floating)):
            xq, xs = quantize_activations(x, w.abits)
            y = jax.lax.ragged_dot(xq.astype(jnp.float32),
                                   wd.astype(jnp.float32), group_sizes)
            return (y * xs).astype(x.dtype)
        w = wd.astype(x.dtype)
    return jax.lax.ragged_dot(x, w, group_sizes)


def einsum_q(spec: str, x: jax.Array, w: Any) -> jax.Array:
    """einsum where w may be stacked-quantized (MoE expert einsums).

    When the weight carries ``abits``, the real int path runs: per-token
    quantized activation codes enter the einsum and the per-token scale is
    applied to the output — the same integer-compute-then-scale semantics
    as the LUT-GEMV kernel, not fake-quant."""
    x, w = _apply_act_quant(x, w)
    if isinstance(w, (QTensor, StackedQTensor)):
        wd = dequantize_any(w).astype(x.dtype)
        if (w.abits is not None
                and jnp.issubdtype(x.dtype, jnp.floating)):
            xq, xs = quantize_activations(x, w.abits)
            xs_out = _einsum_scale_to_out(spec, x.shape, xs)
            if xs_out is not None:
                y = jnp.einsum(spec, xq.astype(jnp.float32),
                               wd.astype(jnp.float32))
                return (y * xs_out).astype(x.dtype)
            # spec not output-mappable: fold the scale into the input
            x = (xq.astype(jnp.float32) * xs).astype(x.dtype)
        return jnp.einsum(spec, x, wd)
    return jnp.einsum(spec, x, w)
