"""Device time by layer kind: the scope paths the compiled programs carry.

Every instruction of a compiled program carries, in its metadata, the name
stack it was traced under: ``op_name="jit(ragged_decode_block)/while/body/
closed_call/MistralForCausalLM/model/layers_1/self_attn/q_proj/
dot_general"``.  The module names are flax's (``flax_profile`` wraps every
module method in ``jax.named_scope``), the phase markers are JAX's own
(``jvp(`` forward, ``transpose(jvp(`` backward, ``checkpoint/
rematted_computation`` recompute), and a handful of ``jax.named_scope``
sites stand where no module does (``zero.fetch``, ``optim``, ``moe.*``,
``sample``, ``counts``, ``head.rows``).  A profiler's trace names a device
operation by its HLO line only (``%fusion.285 = bf16[32,14336]
fusion(...)``), whose result name is the instruction's name in the compiled
module.  Three pieces join the two:

- :func:`classify` folds one ``op_name`` into ``(kind, sub, phase)``;
- :func:`scope_table` parses a compiled module's text into ``{instruction
  name: Scope}``, giving an instruction without metadata (the compiler's
  own ``copy``, ``slice-start`` / ``slice-done``, ``bitcast``) the scope of
  its nearest scoped user, else of its nearest scoped operand;
- the registry keeps, for every program an engine dispatches, the jitted
  function (weakly) and the avals of the call that compiled it, and lowers
  and compiles from them only when :func:`tables` is asked: after a call
  that re-uses the trace and returns the executable in memory, so the text
  is that of the program that ran and nothing is compiled.

Off the hot path by construction: :func:`register` runs once a program (the
engines test membership first), reads no clock and keeps no array.

Imports the standard library, ``jax`` and the name-stack walker of
``profiling/flops_profiler.py``; none of the package's engines.
"""
from __future__ import annotations

import json
import re
import threading
import weakref
from typing import (Any, Dict, Iterable, List, Mapping, NamedTuple, Optional,
                    Tuple)

import jax
import numpy as np

from deepspeed_tpu.profiling.flops_profiler import name_parts

KINDS = ("attn", "mlp", "moe", "conv", "linear", "head", "embed", "sample",
         "zero", "optim", "rest")
VIAS = ("own", "user", "operand", "none")
MAX_HOPS = 3

# the scopes that stand where no flax module does (each a
# ``jax.named_scope`` site of this package): wherever one is in a path, it
# names the kind
_SCOPE_KIND = {"zero.fetch": "zero", "optim": "optim", "sample": "sample",
               "counts": "rest", "head.rows": "head"}
_MOE_SCOPE = "moe."
# scopes that split ONE module's time by the kind of its layer (a window
# layer's attention and a full layer's, ``models/trinity.py`` and
# ``models/smallthinker.py``): where one is in a path it leads the sub-scope,
# a Pallas kernel's name behind it
_SPLIT_SCOPES = ("attn_window", "attn_full")
# the module names of the model files -> kinds
_MODULE_KIND = {
    "self_attn": "attn", "attention": "attn", "attn": "attn",
    "mlp": "mlp", "feed_forward": "mlp", "shared_expert": "mlp",
    "shared_expert_gate": "mlp",
    "moe": "moe", "block_sparse_moe": "moe", "experts": "moe",
    "conv": "conv",
    "lightning_attn": "linear",
    # a gated-delta-rule mixer, the short convolution inside it with it
    "linear_attn": "linear",
    "lm_head": "head", "embed_tokens.attend": "head",
    "embed_tokens": "embed", "wte": "embed", "wpe": "embed",
}
# a block's norms count to what they feed (or, Ouro's sandwich norms, to
# what they follow); ``mixer`` and ``ffn`` are resolved per layer by
# :func:`scope_table`, which sees which mixer and which MLP the layer has
_NORM_ROLE = {"input_layernorm": "mixer", "operator_norm": "mixer",
              "attention_layer_norm": "mixer",
              "feedforward_layer_norm": "ffn",
              "post_attention_norm": "mixer", "post_feedforward_norm": "ffn",
              "input_layernorm_2": "mixer",
              "post_attention_layernorm": "ffn", "ffn_norm": "ffn",
              "post_attention_layernorm_2": "ffn",
              "pre_mlp_layernorm": "ffn", "post_mlp_layernorm": "ffn"}
_ROLE_DEFAULT = {"mixer": "attn", "ffn": "mlp"}
_ROLE_KINDS = {"mixer": ("attn", "conv", "linear"), "ffn": ("mlp", "moe")}
_LAYER = re.compile(r"^(layers(_\d+)?|block|h(_\d+)?)$")
_WRAPPED = re.compile(r"^\w+\((.*)\)$")


class Scope(NamedTuple):
    kind: str
    sub: Optional[str]
    phase: str
    via: str = "own"


def _phase(op_name: str) -> str:
    if "rematted_computation" in op_name:
        return "remat"
    if "transpose(" in op_name:
        return "bwd"
    if "jvp(" in op_name:
        return "fwd"
    return "-"


def _unwrap(part: str) -> str:
    """``transpose(jvp(LlamaForCausalLM))`` -> ``LlamaForCausalLM``."""
    while True:
        m = _WRAPPED.match(part)
        if not m:
            return part
        part = m.group(1)


def _walk(op_name: str):
    """What :func:`classify` and :func:`scope_table` both need of a path:
    ``(kind or None, role or None, sub, layer prefix or None, module prefix
    or None)``.  ``role`` is set where the kind waits for the layer's
    contents; the prefixes are the path up to the enclosing block and up to
    the module that named the kind."""
    parts = [_unwrap(p) for p in name_parts(op_name)]
    kernel = (parts[-2] if len(parts) >= 2 and parts[-1] == "pallas_call"
              else None)

    def after(i: int) -> Optional[str]:
        # the next element, unless it is the primitive that ends the path
        if kernel is not None:
            return kernel
        return parts[i + 1] if i + 2 < len(parts) else None

    layer = named = module = None
    for i, p in enumerate(parts):
        base = p.split(".", 1)[0]
        if _LAYER.match(p):
            layer = "/".join(parts[:i + 1])
        elif named is None and (p in _MODULE_KIND or base in _MODULE_KIND):
            # the first module the table knows, and the path up to it
            named = (i, _MODULE_KIND.get(p) or _MODULE_KIND[base])
            module = "/".join(parts[:i] + [base])
    for i, p in enumerate(parts):               # this package's scopes win
        if p.startswith(_MOE_SCOPE):
            return "moe", None, p, layer, module
        if p in _SCOPE_KIND:
            return (_SCOPE_KIND[p], None, p if "." in p or p == "counts"
                    else after(i) or p, layer, None)
    if named is not None:
        i, p = named[0], parts[named[0]]
        sub = after(i) or (p if "." in p else None)
        split = next((q for q in parts if q in _SPLIT_SCOPES), None)
        if split is not None and sub != split:
            sub = f"{split}/{sub}"
        return named[1], None, sub, layer, module
    in_layer = False
    for p in parts:
        base = p.split(".", 1)[0]
        if _LAYER.match(p):
            in_layer = True
        elif base in _NORM_ROLE:
            return None, _NORM_ROLE[base], base, layer, None
        elif base == "norm" and not in_layer:   # the FINAL norm
            return "head", None, "norm", layer, None
        elif base == "early_exit_gate":
            return "rest", None, base, layer, None
    if in_layer:
        # an operation of the block itself (a residual add, a pin): it
        # goes with the block's mixer
        return None, "mixer", None, layer, None
    return "rest", None, None, layer, None


def classify(op_name: str) -> Tuple[str, Optional[str], str]:
    """``(kind, sub, phase)`` of one ``op_name`` (or a jaxpr equation's
    name stack joined by ``/``).  ``kind`` is one of :data:`KINDS`; ``sub``
    the next path element where one exists (``q_proj``, ``moe.route``, a
    Pallas kernel's name); ``phase`` one of ``fwd`` / ``remat`` / ``bwd``,
    ``-`` for a program that differentiates nothing.

    A block's norm, and an operation of the block itself, counts to the
    mixer or the MLP of ITS layer, which one path cannot name: attention
    and the dense MLP here, what the layer holds in :func:`classify_all`."""
    return classify_all([op_name])[op_name]


def classify_all(op_names: Iterable[str]
                 ) -> Dict[str, Tuple[str, Optional[str], str]]:
    """:func:`classify` of every path of ONE program, each seen beside the
    others: a module with ``moe.*`` scopes beneath it is an expert layer
    whatever it is called (OLMoE's ``mlp``, LFM2's ``feed_forward``), so
    its unscoped glue is ``moe`` too, and a block's norm goes to the mixer
    and the MLP its own layer holds."""
    # XLA joins the names of instructions it merged with ``;``: the first
    first = {op: op.split(";", 1)[0] for op in set(op_names)}
    walked = {op: _walk(path) for op, path in first.items()}
    experts = {module for kind, _, _, _, module in walked.values()
               if kind == "moe"}
    layers: Dict[str, Dict[str, str]] = {}
    for kind, _, _, layer, module in walked.values():
        if kind == "mlp" and module in experts:
            kind = "moe"
        for role, kinds in _ROLE_KINDS.items():
            if layer is not None and kind in kinds:
                # an expert layer wins over what its glue alone would say
                if kind == "moe" or role not in layers.get(layer, {}):
                    layers.setdefault(layer, {})[role] = kind
    out = {}
    for op, (kind, role, sub, layer, module) in walked.items():
        if kind is None:
            kind = layers.get(layer, {}).get(role) or _ROLE_DEFAULT[role]
        elif kind == "mlp" and module in experts:
            kind = "moe"
        out[op] = (kind, sub, _phase(first[op]))
    return out


# -- a compiled module's text ------------------------------------------------

_INSTR = re.compile(
    r"^\s+(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\((.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_SHAPE = re.compile(r"\b([a-z]+\d+\w*)\[([\d,]*)\]")


def signature(opcode: str, result_type: str) -> Tuple[str, str]:
    """What tells two instructions of one name apart: the opcode and the
    first array of the result, without its layout."""
    m = _SHAPE.search(result_type)
    return opcode, f"{m.group(1)}[{m.group(2)}]" if m else ""


def _operands(rest: str) -> List[str]:
    """The ``%names`` inside the opcode's own parentheses (``rest`` starts
    after the opening one); attributes after them name computations."""
    depth = 1
    for i, c in enumerate(rest):
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return _OPERAND.findall(rest[:i])
    return _OPERAND.findall(rest)


class ScopeTable(Dict[str, Scope]):
    """``{instruction name: Scope}`` of one compiled module, with each
    instruction's :func:`signature` beside it (``signatures``)."""

    def __init__(self):
        super().__init__()
        self.signatures: Dict[str, Tuple[str, str]] = {}


def _nearest(start: str, edges: Mapping[str, List[str]],
             own: Mapping[str, str]) -> Optional[str]:
    """The ``op_name`` of the nearest scoped instruction along ``edges``,
    breadth first in the text's order, at most :data:`MAX_HOPS` away."""
    seen, frontier = {start}, [start]
    for _ in range(MAX_HOPS):
        nxt: List[str] = []
        for name in frontier:
            for other in edges.get(name, ()):
                if other in seen:
                    continue
                if other in own:
                    return own[other]
                seen.add(other)
                nxt.append(other)
        frontier = nxt
    return None


def _through_loops(opcode, operands, users, body_of, index_of, root_of,
                   params_of) -> None:
    """Add, to ``users`` and ``operands``, the edges a ``while`` hides: a
    value that enters a loop in position ``k`` of its operand tuple is read
    inside by the body's ``get-tuple-element(parameter), index=k``; what
    the body's root tuple holds in position ``k`` is read there by the next
    iteration, and after the loop by the ``while``'s own ``index=k``.  So a
    weight copied once before a loop finds the layer that reads it, and a
    prefetch started in one iteration the fusion that waits for it in the
    next."""
    def link(src: str, dst: str) -> None:
        users.setdefault(src, []).append(dst)
        operands.setdefault(dst, []).append(src)

    def reads(tuple_like: str) -> Dict[int, List[str]]:
        out: Dict[int, List[str]] = {}
        for g in users.get(tuple_like, ()):
            if opcode.get(g) == "get-tuple-element" and g in index_of:
                out.setdefault(index_of[g], []).append(g)
        return out

    for loop, body in list(body_of.items()):
        inside = {k: g for p in params_of.get(body, ())
                  for k, g in reads(p).items()}
        after = reads(loop)
        entering = [operands[t] for t in operands.get(loop, ())
                    if opcode.get(t) == "tuple"]
        root = root_of.get(body)
        carried = (operands.get(root, [])
                   if root and opcode.get(root) == "tuple" else [])
        for values, readers in ([(v, inside) for v in entering]
                                + [(carried, inside), (carried, after)]):
            for k, src in enumerate(values):
                for g in readers.get(k, ()):
                    link(src, g)


_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$")
_BODY = re.compile(r"\bbody=%([\w.\-]+)")
_INDEX = re.compile(r"\bindex=(\d+)")


def scope_table(compiled_text: str) -> ScopeTable:
    """Every instruction of a compiled module (``Compiled.as_text()``) with
    its scope.  Fusions, ``while``s and custom calls carry an ``op_name`` of
    their own (``via="own"``); an instruction without one takes the scope of
    its first scoped user, users of users next, at most three hops
    (``"user"``: a ``slice-start`` -> ``slice-done`` -> the fusion that
    reads the slice), then of its first scoped operand's producer the same
    way (``"operand"``), else ``rest`` with ``via="none"``.  A ``while``
    lends its scope to nothing (it encloses every layer), and a value that
    crosses its edge is followed inside (:func:`_through_loops`)."""
    own: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    users: Dict[str, List[str]] = {}
    opcode: Dict[str, str] = {}
    body_of: Dict[str, str] = {}
    index_of: Dict[str, int] = {}
    root_of: Dict[str, str] = {}
    params_of: Dict[str, List[str]] = {}
    loops_own: Dict[str, str] = {}
    table = ScopeTable()
    comp = ""
    for line in compiled_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            c = _COMPUTATION.match(line)
            if c:
                comp = c.group(1)
            continue
        name, result, op, rest = m.groups()
        table.signatures[name] = signature(op, result)
        opcode[name] = op
        ops = _operands(rest)
        operands[name] = ops
        for o in ops:
            users.setdefault(o, []).append(name)
        if "ROOT %" in line[:line.index("=")]:
            root_of[comp] = name
        if op == "parameter":
            params_of.setdefault(comp, []).append(name)
        elif op == "get-tuple-element":
            k = _INDEX.search(rest)
            if k:
                index_of[name] = int(k.group(1))
        elif op == "while":
            b = _BODY.search(rest)
            if b:
                body_of[name] = b.group(1)
        meta = _OP_NAME.search(rest)
        if meta and meta.group(1):
            if op == "while":
                loops_own[name] = meta.group(1)     # lent to nothing
            else:
                own[name] = meta.group(1)
    _through_loops(opcode, operands, users, body_of, index_of, root_of,
                   params_of)
    via: Dict[str, Tuple[str, str]] = {}
    for name in table.signatures:
        if name in own or name in loops_own:
            via[name] = (own.get(name) or loops_own[name], "own")
            continue
        for how, edges in (("user", users), ("operand", operands)):
            found = _nearest(name, edges, own)
            if found is not None:
                via[name] = (found, how)
                break
    kinds = classify_all(v[0] for v in via.values())
    for name in table.signatures:
        if name in via:
            op, how = via[name]
            table[name] = Scope(*kinds[op], how)
        else:
            table[name] = Scope("rest", None, "-", "none")
    return table


# -- the registry ------------------------------------------------------------

def _aval(x: Any) -> Any:
    """Shape, dtype and (where the array was committed to one) sharding of
    an argument; a Python scalar or a static value stays what it is."""
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=bool(getattr(x, "weak_type", False)),
            sharding=x.sharding if getattr(x, "committed", False) else None)
    if isinstance(x, (np.ndarray, np.generic)):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


class Registry:
    """The programs an engine dispatched, each with the avals of the call
    that compiled it.  Holds a jitted function weakly: an engine that is
    gone takes its entries with it."""

    def __init__(self):
        self._lock = threading.Lock()
        self._programs: Dict[str, List[Tuple[Any, Tuple, Dict]]] = {}

    def register(self, program: str, jitted: Any, args: Iterable[Any] = (),
                 kwargs: Optional[Mapping[str, Any]] = None) -> None:
        """Note ``jitted`` under ``program`` (its name in a trace without
        the ``jit_``) with the arguments of the call about to compile it.
        Two variants of one name are two entries."""
        if not hasattr(jitted, "lower"):
            return                          # not a jitted function
        entry = (weakref.ref(jitted), jax.tree_util.tree_map(_aval,
                                                             tuple(args)),
                 jax.tree_util.tree_map(_aval, dict(kwargs or {})))
        with self._lock:
            rows = self._programs.setdefault(program, [])
            rows[:] = [r for r in rows if r[0]() is not None]
            if not any(r[0]() is jitted for r in rows):
                rows.append(entry)

    def programs(self) -> Dict[str, int]:
        """``{program: live variants}``."""
        with self._lock:
            return {p: n for p, n in (
                (p, sum(r[0]() is not None for r in rows))
                for p, rows in self._programs.items()) if n}

    def texts(self) -> Dict[str, List[str]]:
        """Each live variant's compiled text.  ``lower`` on the avals of
        the call that ran re-uses its trace, ``compile`` returns the
        executable the call compiled."""
        with self._lock:
            rows = {p: list(r) for p, r in self._programs.items()}
        out: Dict[str, List[str]] = {}
        for program, entries in rows.items():
            for ref, args, kwargs in entries:
                fn = ref()
                if fn is not None:
                    out.setdefault(program, []).append(
                        fn.lower(*args, **kwargs).compile().as_text())
        return out

    def tables(self) -> Dict[str, List[ScopeTable]]:
        """``{program: [scope table of each variant]}``, built now."""
        return {p: [scope_table(t) for t in texts]
                for p, texts in self.texts().items()}

    def dump(self, path: str) -> str:
        """The tables as JSON: ``{program: [{instruction: [kind, sub,
        phase, via]}]}``, for joining with any profile viewer by the
        operation's name."""
        with open(path, "w") as f:
            json.dump({p: [{n: list(s) for n, s in t.items()} for t in ts]
                       for p, ts in self.tables().items()}, f)
        return path


_REGISTRY = Registry()
register = _REGISTRY.register
programs = _REGISTRY.programs
tables = _REGISTRY.tables
dump = _REGISTRY.dump


def lookup(tables_of_program: List[ScopeTable], name: str,
           sig: Optional[Tuple[str, str]] = None) -> Optional[Scope]:
    """An operation's scope among the variants of its program: the first
    variant that holds ``name`` with the same opcode and shape, else the
    first that holds the name at all."""
    held = [t for t in tables_of_program if name in t]
    if sig is not None:
        for t in held:
            if t.signatures.get(name) == sig:
                return t[name]
    return held[0][name] if held else None
