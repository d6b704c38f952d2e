"""FLOPs profiler: per-module FLOPs / MACs / params for any jittable fn.

TPU-native re-design of the reference flops profiler
(``profiling/flops_profiler/profiler.py:30 FlopsProfiler``, ``:1106
get_model_profile``).  The reference monkey-patches every
``torch.nn.functional`` to accumulate counts into module attributes as the
eager graph runs.  Under JAX the program IS data: we trace the function
once to a jaxpr and fold a FLOPs cost over its equations — no patching, no
runtime overhead, exact trip counts for ``scan`` — and attribute each
equation to its originating ``flax`` module via the compiler name stack
(``nn.Module`` scopes become ``named_scope`` entries on every equation).

Costs follow the reference's conventions (``profiler.py:518-806``): a
matmul is ``2 * out_numel * K`` FLOPs (MACs = half), convs count
``2 * out_numel * (Cin/groups * prod(kernel))``, elementwise/reduction ops
count one FLOP per output element, and everything unrecognized counts 0 —
the same "model FLOPs" definition BASELINE.md's MFU numbers use.

API mirrors the reference: :func:`get_model_profile` returns
``(flops, macs, params)`` for a flax module, and :class:`FlopsProfiler`
wraps an engine with ``start_profile / stop_profile / print_model_profile``
driven by ``flops_profiler`` config (``profile_step``, ``module_depth``,
``top_modules``, ``detailed``, ``output_file``).
"""
from __future__ import annotations

import math
import sys
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

# elementwise / reduction primitives billed at 1 FLOP per output element
_ONE_PER_ELEMENT = {
    "add", "sub", "mul", "div", "rem", "max", "min", "pow", "neg", "abs",
    "exp", "log", "log1p", "expm1", "tanh", "logistic", "erf", "erf_inv",
    "erfc", "rsqrt", "sqrt", "cbrt", "sin", "cos", "tan", "sign", "floor",
    "ceil", "round", "integer_pow", "atan2", "and", "or", "xor", "not",
    "select_n", "clamp", "nextafter", "square",
}
_REDUCE = {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
           "reduce_and", "reduce_or", "argmax", "argmin",
           "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp"}
# structural ops: 0 FLOPs (data movement; billed as bytes, not flops)
_CALL_PRIMS = {"pjit", "jit", "closed_call", "core_call", "remat",
               "checkpoint", "custom_jvp_call", "custom_vjp_call",
               "custom_vjp_call_jaxpr", "custom_jvp_call_jaxpr",
               "shard_map", "smap", "xla_call"}


def _numel(shape) -> int:
    return int(np.prod(shape)) if shape else 1


@dataclass
class _Node:
    """Aggregated cost for one module-path prefix."""
    flops: float = 0.0
    macs: float = 0.0
    params: int = 0
    children: Dict[str, "_Node"] = field(default_factory=dict)

    def child(self, name: str) -> "_Node":
        return self.children.setdefault(name, _Node())


def _dot_cost(eqn) -> Tuple[float, float]:
    (lhs_c, _), _ = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    out = eqn.outvars[0].aval.shape
    k = _numel([lhs[d] for d in lhs_c])
    macs = _numel(out) * k
    return 2.0 * macs, float(macs)


def _conv_cost(eqn) -> Tuple[float, float]:
    rhs = eqn.invars[1].aval.shape  # kernel
    out = eqn.outvars[0].aval.shape
    dn = eqn.params["dimension_numbers"]
    groups = int(eqn.params.get("feature_group_count", 1))
    kernel_spatial = [rhs[d] for d in dn.rhs_spec[2:]]
    del groups  # rhs channel dim is already Cin/groups in lax convention
    cin = rhs[dn.rhs_spec[1]]
    macs = _numel(out) * cin * _numel(kernel_spatial)
    return 2.0 * macs, float(macs)


def _eqn_cost(eqn) -> Tuple[float, float]:
    """(flops, macs) of one non-call equation."""
    name = eqn.primitive.name
    if name == "dot_general":
        return _dot_cost(eqn)
    if name in ("conv_general_dilated",):
        return _conv_cost(eqn)
    out_numel = _numel(eqn.outvars[0].aval.shape) if eqn.outvars else 0
    if name in _ONE_PER_ELEMENT:
        return float(out_numel), 0.0
    if name in _REDUCE:
        return float(_numel(eqn.invars[0].aval.shape)), 0.0
    if name in ("reduce_precision", "convert_element_type"):
        return 0.0, 0.0
    return 0.0, 0.0


def _accumulate(root: _Node, path: List[str], flops: float,
                macs: float) -> None:
    node = root
    node.flops += flops
    node.macs += macs
    for part in path:
        node = node.child(part)
        node.flops += flops
        node.macs += macs


def name_parts(stack: str) -> List[str]:
    """A name stack (an equation's, or the ``op_name`` a compiled
    instruction carries) as its elements: split at ``/`` outside
    parentheses, so ``transpose(jvp(Model))`` stays one element."""
    parts, depth, start = [], 0, 0
    for i, c in enumerate(stack):
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        elif c == "/" and depth == 0:
            parts.append(stack[start:i])
            start = i + 1
    parts.append(stack[start:])
    return [p for p in parts if p]


def _sub_jaxpr(eqn, repeat: float):
    """``(jaxpr an equation calls or None, how often it runs)``."""
    name, params = eqn.primitive.name, eqn.params
    if name == "scan":
        return params["jaxpr"].jaxpr, repeat * int(params.get("length", 1))
    if name == "while":
        # trip count is dynamic; bill one iteration (documented)
        return params["body_jaxpr"].jaxpr, repeat
    if "jaxpr" in params:
        # closed (pjit, custom_jvp) or open (``checkpoint``: the forward
        # run again inside a backward pass is work the device does)
        sub = params["jaxpr"]
        sub = getattr(sub, "jaxpr", sub)
        if hasattr(sub, "eqns"):
            return sub, repeat
    if "call_jaxpr" in params:
        cj = params["call_jaxpr"]
        return (cj.jaxpr if hasattr(cj, "jaxpr") else cj), repeat
    if "fun_jaxpr" in params:
        return params["fun_jaxpr"].jaxpr, repeat
    return None, repeat


def walk_equations(jaxpr, prefix: Tuple[str, ...] = (), repeat: float = 1.0,
                   pick_branches=None):
    """Every equation of ``jaxpr`` that calls no other, as ``(equation,
    module path, how often it runs)``, through scans (times their
    length), whiles (once), calls and ``cond`` branches (all of them, or
    those ``pick_branches(branches, path, repeat)`` returns).  The path is
    the name stack: a sub-jaxpr's stacks are relative to the call site,
    hence the prefix threading."""
    for eqn in jaxpr.eqns:
        stack = str(eqn.source_info.name_stack)
        path = tuple(prefix) + tuple(name_parts(stack) if stack else ())
        if eqn.primitive.name == "cond":
            branches = eqn.params["branches"]
            if pick_branches is not None:
                branches = pick_branches(branches, path, repeat)
            for br in branches:
                yield from walk_equations(br.jaxpr, path, repeat,
                                          pick_branches)
            continue
        sub, factor = _sub_jaxpr(eqn, repeat)
        if sub is not None:
            yield from walk_equations(sub, path, factor, pick_branches)
        else:
            yield eqn, list(path), factor


def _dearest_branch(branches, path, repeat):
    """Bill the most expensive branch of a ``cond``."""
    costs = []
    for br in branches:
        tmp = _Node()
        _walk(br.jaxpr, tmp, list(path), repeat)
        costs.append(tmp.flops)
    return [branches[costs.index(max(costs))]] if costs else []


def _walk(jaxpr, root: _Node, prefix: List[str], repeat: float) -> None:
    # costs stay rooted at `root`
    for eqn, path, factor in walk_equations(jaxpr, tuple(prefix), repeat,
                                            _dearest_branch):
        flops, macs = _eqn_cost(eqn)
        _accumulate(root, path, flops * factor, macs * factor)


def _merge(dst: _Node, src: _Node) -> None:
    dst.flops += src.flops
    dst.macs += src.macs
    for k, v in src.children.items():
        _merge(dst.child(k), v)


def _param_counts(params: Any, root: _Node,
                  root_name: Optional[str] = None) -> None:
    if params is None:
        return
    import jax.tree_util as jtu

    flat = jtu.tree_flatten_with_path(params)[0]
    for kp, leaf in flat:
        if not hasattr(leaf, "size"):
            continue
        parts = [str(getattr(k, "key", getattr(k, "idx", k))) for k in kp]
        # drop the collection name ('params') and prepend the root module
        # scope so paths line up with the name-stack module paths
        if parts and parts[0] in ("params", "batch_stats", "cache"):
            parts = parts[1:]
        if root_name:
            parts = [root_name] + parts
        node = root
        node.params += int(leaf.size)
        for p in parts[:-1]:  # last part is the leaf array name
            node = node.child(p)
            node.params += int(leaf.size)


def profile_fn(fn: Callable, *args, params: Any = None,
               root_name: Optional[str] = None,
               static_argnums=(), **kwargs) -> _Node:
    """Trace ``fn(*args, **kwargs)`` and return the module-path cost tree
    (flops/macs per flax scope; params attributed when ``params`` given)."""
    closed = jax.make_jaxpr(fn, static_argnums=static_argnums)(
        *args, **kwargs)
    root = _Node()
    _walk(closed.jaxpr, root, [], 1.0)
    _param_counts(params, root, root_name)
    return root


# ---------------------------------------------------------------------------
# human-readable output (reference profiler.py:845-906 formatting helpers)
# ---------------------------------------------------------------------------

def _si(val: float, unit: str = "") -> str:
    for scale, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")):
        if abs(val) >= scale:
            return f"{val / scale:.2f} {suffix}{unit}"
    return f"{val:.2f} {unit}"


def params_to_string(n: int) -> str:
    return _si(float(n)).strip()


def flops_to_string(n: float) -> str:
    return _si(n, "FLOPs")


def macs_to_string(n: float) -> str:
    return _si(n, "MACs")


class FlopsProfiler:
    """Engine-attached profiler (reference ``FlopsProfiler`` surface).

    ``start_profile()`` arms it; the engine calls :meth:`profile_step`
    once per step with the step callable + args; at the configured
    ``profile_step`` the cost tree is computed and
    :meth:`print_model_profile` renders the breakdown.
    """

    def __init__(self, fn: Optional[Callable] = None, ds_engine=None,
                 recompute_fwd_factor: float = 0.0):
        self.fn = fn
        self.ds_engine = ds_engine
        self.recompute_fwd_factor = recompute_fwd_factor
        self.started = False
        self._tree: Optional[_Node] = None
        self._duration: float = 0.0

    def start_profile(self, ignore_list=None) -> None:
        self.started = True
        self._tree = None

    def stop_profile(self) -> None:
        self.started = False

    def end_profile(self) -> None:
        self.stop_profile()
        self._tree = None

    # -- measurement --------------------------------------------------

    def profile(self, *args, params: Any = None, duration: float = 0.0,
                root_name: Optional[str] = None, **kwargs) -> None:
        assert self.fn is not None, "no function to profile"
        self._tree = profile_fn(self.fn, *args, params=params,
                                root_name=root_name, **kwargs)
        self._duration = duration

    # -- accessors (reference names) ----------------------------------

    def get_total_flops(self, as_string: bool = False):
        t = self._tree.flops if self._tree else 0.0
        return flops_to_string(t) if as_string else t

    def get_total_macs(self, as_string: bool = False):
        t = self._tree.macs if self._tree else 0.0
        return macs_to_string(t) if as_string else t

    def get_total_params(self, as_string: bool = False):
        t = self._tree.params if self._tree else 0
        return params_to_string(t) if as_string else t

    def get_total_duration(self, as_string: bool = False):
        return (f"{self._duration * 1e3:.2f} ms" if as_string
                else self._duration)

    # -- rendering ----------------------------------------------------

    def print_model_profile(self, profile_step: int = 1,
                            module_depth: int = -1, top_modules: int = 1,
                            detailed: bool = True,
                            output_file: Optional[str] = None) -> None:
        if self._tree is None:
            return
        out = open(output_file, "w") if output_file else sys.stdout
        try:
            total = self._tree
            print("-" * 72, file=out)
            print("DeepSpeed-TPU Flops Profiler", file=out)
            print(f"profile step:                   {profile_step}",
                  file=out)
            print(f"params:                         "
                  f"{params_to_string(total.params)}", file=out)
            print(f"fwd+bwd+step flops:             "
                  f"{flops_to_string(total.flops)}", file=out)
            print(f"fwd+bwd+step MACs:              "
                  f"{macs_to_string(total.macs)}", file=out)
            if self._duration > 0:
                print(f"step latency:                   "
                      f"{self._duration * 1e3:.2f} ms", file=out)
                print(f"achieved:                       "
                      f"{_si(total.flops / self._duration, 'FLOPS')}",
                      file=out)
            if detailed:
                print("\nper-module breakdown "
                      "(flops | MACs | params):", file=out)
                self._print_tree(total, out, depth=0,
                                 max_depth=module_depth, top_modules=0)
            if top_modules > 0:
                print(f"\ntop {top_modules} modules per depth by flops:",
                      file=out)
                self._print_tree(total, out, depth=0,
                                 max_depth=module_depth,
                                 top_modules=top_modules)
            print("-" * 72, file=out)
        finally:
            if output_file:
                out.close()

    def print_model_aggregated_profile(self, module_depth: int = -1,
                                       top_modules: int = 1) -> None:
        self.print_model_profile(module_depth=module_depth,
                                 top_modules=top_modules, detailed=True)

    def _print_tree(self, node: _Node, out, depth: int, max_depth: int,
                    top_modules: int, name: str = "model") -> None:
        if max_depth >= 0 and depth > max_depth:
            return
        pad = "  " * depth
        print(f"{pad}{name}: {flops_to_string(node.flops)} | "
              f"{macs_to_string(node.macs)} | "
              f"{params_to_string(node.params)}", file=out)
        ranked = sorted(node.children.items(),
                        key=lambda kv: kv[1].flops, reverse=True)
        # top_modules bounds how many children print per level (reference
        # print_model_aggregated_profile semantics); <=0 means all
        limit = len(ranked) if top_modules <= 0 else min(top_modules,
                                                         len(ranked))
        for child_name, child in ranked[:limit]:
            self._print_tree(child, out, depth + 1, max_depth,
                             top_modules, child_name)


def get_model_profile(model, input_shape: Optional[Tuple[int, ...]] = None,
                      args: Tuple = (), kwargs: Optional[Dict] = None,
                      print_profile: bool = True, detailed: bool = True,
                      module_depth: int = -1, top_modules: int = 1,
                      as_string: bool = True,
                      output_file: Optional[str] = None,
                      rng=None):
    """Profile a flax module's forward pass; returns (flops, macs, params)
    — the reference ``get_model_profile`` contract
    (``flops_profiler/profiler.py:1106``)."""
    import jax.numpy as jnp

    kwargs = kwargs or {}
    if input_shape is not None:
        assert not args, "pass input_shape or args, not both"
        args = (jnp.ones(input_shape, jnp.float32),)
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    # abstract trace only: ShapeDtypeStructs never allocate, so 7B-class
    # modules profile without materializing parameters
    variables = jax.eval_shape(lambda: model.init(rng, *args, **kwargs))

    prof = FlopsProfiler(lambda v, *a: model.apply(v, *a, **kwargs))
    prof.start_profile()
    prof.profile(variables, *args,
                 params=variables.get("params", variables),
                 root_name=type(model).__name__)
    if print_profile:
        prof.print_model_profile(module_depth=module_depth,
                                 top_modules=top_modules,
                                 detailed=detailed,
                                 output_file=output_file)
    flops, macs, params = (prof.get_total_flops(as_string),
                           prof.get_total_macs(as_string),
                           prof.get_total_params(as_string))
    prof.end_profile()
    return flops, macs, params
