"""Transfer models for builtins, ``jnp``/``np``/``lax``/``pl`` and methods.

Each model is small and conservative: anything unmodeled returns TOP (with
the tile flag propagated), so unknown library surface can only lose
precision, never soundness.  Shape-sensitive constructors/reshapers call
into :mod:`shapes` for the symbolic checks; ``pl.pallas_call`` and
``jax.vmap`` produce first-class values whose *invocation* is checked
(pallas_checks.py / shapes.py).
"""

from __future__ import annotations

import ast
import dataclasses

from .absdom import DTYPE_WIDTH, INT_DTYPES, Dim, IVal, dim_of, join_all, rem
from .interp import (TOP, BlockSpecVal, BoundMethod, BuiltinVal, ConstVal,
                     DtypeVal, Event, FuncVal, LVal, PallasVal, RangeVal,
                     StructVal, SymVal, TVal, VmapVal)


def _tile_of(*vals) -> bool:
    return any(getattr(v, "tile", False) for v in vals)


def _as_ival(v) -> IVal:
    if isinstance(v, IVal):
        return v
    if isinstance(v, SymVal):
        return IVal(0, None)
    return IVal(tile=_tile_of(v))


def cast(v, dtype: str) -> IVal:
    """``astype``/dtype-constructor semantics: keep the interval when it
    provably fits the target, else the target's full range."""
    iv = _as_ival(v)
    if dtype not in INT_DTYPES:
        return IVal(dtype=dtype, tile=iv.tile, shape=iv.shape)
    if iv.fits(dtype) is True:
        return dataclasses.replace(iv, dtype=dtype)
    return dataclasses.replace(IVal.top(dtype, tile=iv.tile), shape=iv.shape)


def _shape_from_value(v) -> tuple | None:
    """A shape tuple of Dims from an abstract shape argument.

    A single *unknown* scalar stays None: an opaque value in shape position
    may itself be a tuple (``batch + (n, m)`` with unknown batch), so
    assuming rank 1 would fabricate provably-wrong ranks."""
    if isinstance(v, (TVal, LVal)):
        elems = v.elems if isinstance(v, TVal) else (v.elems if v.concrete else None)
        if elems is None:
            return None
        return tuple(dim_of(e) for e in elems)
    if isinstance(v, SymVal):
        return (v.dim,)
    if isinstance(v, IVal) and v.is_const:
        return (dim_of(v),)
    return None


def _dtype_from(v) -> str | None:
    if isinstance(v, DtypeVal):
        return v.name
    if isinstance(v, ConstVal) and isinstance(v.value, str):
        return v.value if v.value in DTYPE_WIDTH else None
    return None


# -- python builtins ----------------------------------------------------------


def _b_len(interp, args, kwargs, node, env, mod):
    (v,) = args or (TOP,)
    if isinstance(v, LVal):
        return v.length if not v.concrete else IVal.const(len(v.elems))
    if isinstance(v, TVal):
        return IVal.const(len(v.elems))
    if isinstance(v, IVal) and v.shape:
        return _dim_len(v.shape[0])
    return IVal(0, None)  # len() is a host int, never a tile


def _dim_len(d: Dim):
    return IVal.const(d.coeff) if d.is_const else SymVal(d)


def _b_range(interp, args, kwargs, node, env, mod):
    ivs = [_as_ival(a) if not isinstance(a, SymVal) else a for a in args]
    if len(ivs) == 1:
        return RangeVal(IVal.const(0), ivs[0], IVal.const(1))
    if len(ivs) == 2:
        return RangeVal(ivs[0] if isinstance(ivs[0], IVal) else IVal(0, None),
                        ivs[1], IVal.const(1))
    if len(ivs) == 3:
        return RangeVal(ivs[0] if isinstance(ivs[0], IVal) else IVal(0, None),
                        ivs[1],
                        ivs[2] if isinstance(ivs[2], IVal) else IVal.const(1))
    return RangeVal(IVal.const(0), TOP, IVal.const(1))


def _b_int(interp, args, kwargs, node, env, mod):
    v = args[0] if args else IVal.const(0)
    iv = _as_ival(v)
    return IVal.range(iv.lo, iv.hi)  # host int: loses dtype AND tile


def _b_minmax(is_min):
    def run(interp, args, kwargs, node, env, mod):
        vals = args
        if len(vals) == 1 and isinstance(vals[0], (LVal, TVal, RangeVal)):
            mode, data = interp._iter_values(vals[0])
            vals = data if mode == "concrete" else [data]
        ivs = [_as_ival(v) for v in vals]
        if not ivs:
            return TOP
        out = ivs[0]
        for v in ivs[1:]:
            if is_min:
                lo = None if out.lo is None or v.lo is None else min(out.lo, v.lo)
                hi = None if out.hi is None or v.hi is None else min(out.hi, v.hi)
            else:
                lo = None if out.lo is None or v.lo is None else max(out.lo, v.lo)
                hi = None if out.hi is None or v.hi is None else max(out.hi, v.hi)
            out = IVal.range(lo, hi, None, out.tile or v.tile)
        return out
    return run


def _b_abs(interp, args, kwargs, node, env, mod):
    v = _as_ival(args[0]) if args else TOP
    if v.lo is None or v.hi is None:
        return IVal(0, None, None, v.dtype, v.tile)
    lo = 0 if v.lo <= 0 <= v.hi else min(abs(v.lo), abs(v.hi))
    return IVal.range(lo, max(abs(v.lo), abs(v.hi)), v.dtype, v.tile)


def _b_pow(interp, args, kwargs, node, env, mod):
    ivs = [_as_ival(a) for a in args]
    if len(ivs) >= 2 and all(v.is_const for v in ivs[:3] if v is not None):
        try:
            if len(ivs) == 3:
                return IVal.const(pow(ivs[0].lo, ivs[1].lo, ivs[2].lo))
            if 0 <= ivs[1].lo <= 64 and abs(ivs[0].lo) <= 2**20:
                return IVal.const(pow(ivs[0].lo, ivs[1].lo))
        except (ValueError, ZeroDivisionError):
            return TOP
    return IVal(tile=_tile_of(*args))


def _b_sum(interp, args, kwargs, node, env, mod):
    if args and isinstance(args[0], (LVal, TVal)):
        mode, data = interp._iter_values(args[0])
        if mode == "concrete":
            total = IVal.const(0)
            from .absdom import add
            for v in data:
                total = add(total, _as_ival(v))
            return total
    return IVal(tile=_tile_of(*args))


def _b_zip(interp, args, kwargs, node, env, mod):
    cols = []
    for a in args:
        mode, data = interp._iter_values(a)
        if mode != "concrete":
            elem = TVal(tuple(interp._iter_values(x)[1] for x in args))
            return LVal(elem=elem, length=IVal(0, None))
        cols.append(data)
    n = min((len(c) for c in cols), default=0)
    return LVal([TVal(tuple(c[i] for c in cols)) for i in range(n)])


def _b_enumerate(interp, args, kwargs, node, env, mod):
    if not args:
        return TOP
    mode, data = interp._iter_values(args[0])
    if mode == "concrete":
        return LVal([TVal((IVal.const(i), v)) for i, v in enumerate(data)])
    return LVal(elem=TVal((IVal(0, None), data)), length=IVal(0, None))


def _b_list(interp, args, kwargs, node, env, mod):
    if not args:
        return LVal([])
    mode, data = interp._iter_values(args[0])
    return LVal(list(data)) if mode == "concrete" else LVal(elem=data,
                                                           length=IVal(0, None))


def _b_tuple(interp, args, kwargs, node, env, mod):
    v = _b_list(interp, args, kwargs, node, env, mod)
    return TVal(tuple(v.elems)) if isinstance(v, LVal) and v.concrete else v


def _b_reversed(interp, args, kwargs, node, env, mod):
    if args:
        mode, data = interp._iter_values(args[0])
        if mode == "concrete":
            return LVal(list(reversed(data)))
        return args[0]
    return TOP


def _b_bool_like(interp, args, kwargs, node, env, mod):
    return IVal.range(0, 1, "bool", _tile_of(*args))


_BUILTIN_MODELS = {
    "len": _b_len, "range": _b_range, "int": _b_int, "min": _b_minmax(True),
    "max": _b_minmax(False), "abs": _b_abs, "pow": _b_pow, "sum": _b_sum,
    "zip": _b_zip, "enumerate": _b_enumerate, "list": _b_list,
    "tuple": _b_tuple, "reversed": _b_reversed, "sorted": _b_list,
    "isinstance": _b_bool_like, "hasattr": _b_bool_like, "bool": _b_bool_like,
    "all": _b_bool_like, "any": _b_bool_like,
}


# -- jnp / np / lax / jax / pl ------------------------------------------------


def _j_where(interp, args, kwargs, node, env, mod):
    if len(args) == 3:
        a, b = _as_ival(args[1]), _as_ival(args[2])
        out = a.join(b)
        return dataclasses.replace(out, tile=out.tile or _tile_of(args[0]))
    return IVal(tile=_tile_of(*args))


def _j_minimum(interp, args, kwargs, node, env, mod):
    return _b_minmax(True)(interp, args, kwargs, node, env, mod)


def _j_maximum(interp, args, kwargs, node, env, mod):
    return _b_minmax(False)(interp, args, kwargs, node, env, mod)


def _j_zeros(fill: int | None):
    def run(interp, args, kwargs, node, env, mod):
        shape = _shape_from_value(args[0]) if args else None
        dtype = _dtype_from(kwargs.get("dtype") or (args[1] if len(args) > 1 else None))
        if fill is None:  # jnp.full(shape, value)
            v = _as_ival(args[1]) if len(args) > 1 else TOP
            dtype = _dtype_from(kwargs.get("dtype") or (args[2] if len(args) > 2 else None))
            base = IVal.range(v.lo, v.hi, dtype, True)
        else:
            base = IVal.const(fill, dtype, True) if dtype is None or dtype in INT_DTYPES \
                else IVal(dtype=dtype, tile=True)
        if dtype and dtype not in INT_DTYPES:
            base = IVal(dtype=dtype, tile=True)
        return dataclasses.replace(base, dtype=dtype, shape=shape)
    return run


def _j_like(fill: int | None):
    def run(interp, args, kwargs, node, env, mod):
        src = _as_ival(args[0]) if args else TOP
        if fill is None:  # full_like
            v = _as_ival(args[1]) if len(args) > 1 else TOP
            base = IVal.range(v.lo, v.hi, src.dtype, True)
        elif src.dtype and src.dtype not in INT_DTYPES:
            base = IVal(dtype=src.dtype, tile=True)
        else:
            base = IVal.const(fill, src.dtype, True)
        return dataclasses.replace(base, shape=src.shape)
    return run


def _j_arange(interp, args, kwargs, node, env, mod):
    ivs = [_as_ival(a) for a in args]
    dtype = _dtype_from(kwargs.get("dtype"))
    if len(ivs) == 1 and ivs[0].hi is not None:
        n = ivs[0]
        shape = (dim_of(n),) if n.is_const else None
        return IVal.range(0, max(n.hi - 1, 0), dtype, True) if dtype is None or \
            dtype in INT_DTYPES else IVal(dtype=dtype, tile=True, shape=shape)
    return IVal(tile=True, dtype=dtype)


def _j_pad(interp, args, kwargs, node, env, mod):
    src = _as_ival(args[0]) if args else TOP
    fill = kwargs.get("constant_values")
    if fill is None and len(args) <= 2 and not kwargs.get("mode"):
        out = src.join(IVal.const(0))  # default zero padding joins 0
    elif isinstance(fill, IVal):
        out = src.join(fill)
    else:  # non-constant fill / edge modes: values stay within src for
        # edge/reflect, but be conservative about anything unmodeled
        out = src.join(_as_ival(fill)) if fill is not None else IVal(tile=True)
    return dataclasses.replace(out, dtype=src.dtype, tile=True, shape=None)


def _j_reshape(interp, args, kwargs, node, env, mod):
    from . import shapes
    src = _as_ival(args[0]) if args else TOP
    dim_args = args[1:]
    if len(dim_args) == 1:
        # a single argument may be a full shape tuple (possibly opaque)
        shp = _shape_from_value(dim_args[0])
    elif dim_args:
        # multiple arguments are scalar dims by signature: rank is known
        shp = tuple(dim_of(a) for a in dim_args)
    else:
        shp = None
    new_shape = shapes.check_reshape(interp, src, shp, node, mod)
    return dataclasses.replace(src, shape=new_shape)


def _j_concatenate(interp, args, kwargs, node, env, mod):
    from . import shapes
    parts = []
    if args and isinstance(args[0], (LVal, TVal)):
        mode, data = interp._iter_values(args[0])
        parts = data if mode == "concrete" else []
    axis = kwargs.get("axis") or (args[1] if len(args) > 1 else None)
    axis_c = axis.lo if isinstance(axis, IVal) and axis.is_const else 0
    new_shape = shapes.check_concatenate(interp, parts, axis_c, node, mod)
    ivs = [_as_ival(p) for p in parts]
    out = join_all(ivs) if ivs else TOP
    return dataclasses.replace(out, tile=True, shape=new_shape)


def _j_stack(interp, args, kwargs, node, env, mod):
    parts = []
    if args and isinstance(args[0], (LVal, TVal)):
        mode, data = interp._iter_values(args[0])
        parts = data if mode == "concrete" else []
    ivs = [_as_ival(p) for p in parts]
    out = join_all(ivs) if ivs else TOP
    return dataclasses.replace(out, tile=True, shape=None)


def _j_transpose(interp, args, kwargs, node, env, mod):
    from . import shapes
    src = _as_ival(args[0]) if args else TOP
    axes = kwargs.get("axes") or (args[1] if len(args) > 1 else None)
    new_shape = shapes.check_transpose(interp, src, axes, node, mod)
    return dataclasses.replace(src, shape=new_shape)


def _j_swapaxes(interp, args, kwargs, node, env, mod):
    from . import shapes
    src = _as_ival(args[0]) if args else TOP
    new_shape = shapes.check_swapaxes(
        interp, src,
        args[1] if len(args) > 1 else None,
        args[2] if len(args) > 2 else None, node, mod)
    return dataclasses.replace(src, shape=new_shape)


def _j_matmul(interp, args, kwargs, node, env, mod):
    from . import shapes
    a = _as_ival(args[0]) if args else TOP
    b = _as_ival(args[1]) if len(args) > 1 else TOP
    _check_accum_dtype(interp, (a, b), kwargs, node, mod)
    new_shape = shapes.check_matmul(interp, a, b, node, mod)
    return IVal(dtype=None, tile=True, shape=new_shape)


def _j_dot_general(interp, args, kwargs, node, env, mod):
    a = _as_ival(args[0]) if args else TOP
    b = _as_ival(args[1]) if len(args) > 1 else TOP
    _check_accum_dtype(interp, (a, b), kwargs, node, mod)
    return IVal(tile=True)


def _check_accum_dtype(interp, operands, kwargs, node, mod) -> None:
    pref = _dtype_from(kwargs.get("preferred_element_type"))
    if pref is None:
        return
    widths = [DTYPE_WIDTH.get(v.dtype) for v in operands if v.dtype]
    if widths and DTYPE_WIDTH.get(pref, 0) < max(widths):
        interp.events.append(Event(
            "kernel-accum-dtype", mod.path, node,
            f"preferred_element_type={pref} is narrower than the "
            f"{max(widths)}-bit operands: the contraction accumulates in a "
            "narrower type than its inputs and loses precision/overflows"))


def _j_reduce(interp, args, kwargs, node, env, mod):
    src = _as_ival(args[0]) if args else TOP
    return IVal(tile=src.tile or True)


def _j_reduce_minmax(interp, args, kwargs, node, env, mod):
    src = _as_ival(args[0]) if args else TOP
    return dataclasses.replace(src, shape=None)  # element range is preserved


def _j_asarray(interp, args, kwargs, node, env, mod):
    v = args[0] if args else TOP
    dtype = _dtype_from(kwargs.get("dtype") or (args[1] if len(args) > 1 else None))
    if isinstance(v, (LVal, TVal)):
        mode, data = interp._iter_values(v)
        ivs = [_as_ival(x) for x in (data if mode == "concrete" else [data])]
        out = join_all(ivs) if ivs else TOP
        shape = (Dim.const(len(data)),) if mode == "concrete" else None
        out = dataclasses.replace(out, tile=True, shape=shape)
    else:
        out = dataclasses.replace(_as_ival(v), tile=True)
    return cast(out, dtype) if dtype else out


def _j_bit(interp_op):
    def run(interp, args, kwargs, node, env, mod):
        a = _as_ival(args[0]) if args else TOP
        b = _as_ival(args[1]) if len(args) > 1 else TOP
        return interp._binop(interp_op(), a, b, node, env, mod)
    return run


def _jax_jit(interp, args, kwargs, node, env, mod):
    if args and isinstance(args[0], FuncVal):
        fv = args[0]
        donate = ()
        dn = kwargs.get("donate_argnums")
        if isinstance(dn, IVal) and dn.is_const:
            donate = (dn.lo,)
        elif isinstance(dn, (TVal, LVal)):
            mode, data = interp._iter_values(dn)
            if mode == "concrete":
                donate = tuple(d.lo for d in data
                               if isinstance(d, IVal) and d.is_const)
        return FuncVal(fv.node, fv.module, fv.closure, fv.bound_args,
                       fv.bound_kwargs, jitted=True, donate=donate)
    return args[0] if args else TOP


def _jax_vmap(interp, args, kwargs, node, env, mod):
    func = args[0] if args else None
    in_axes = kwargs.get("in_axes") or (args[1] if len(args) > 1 else None)
    out_axes = kwargs.get("out_axes") or (args[2] if len(args) > 2 else None)
    return VmapVal(func, in_axes, out_axes, node)


def _lax_cond(interp, args, kwargs, node, env, mod):
    outs = []
    for branch in args[1:3]:
        if isinstance(branch, FuncVal):
            outs.append(interp.summary(branch))
    ivs = [o for o in outs if isinstance(o, IVal)]
    return join_all(ivs) if ivs and len(ivs) == len(outs) else IVal(tile=True)


def _lax_rem(interp, args, kwargs, node, env, mod):
    if len(args) == 2:
        return rem(_as_ival(args[0]), _as_ival(args[1]))
    return IVal(tile=True)


def _lax_shr_logical(interp, args, kwargs, node, env, mod):
    # a logical shift of a non-negative value is the arithmetic one
    a = _as_ival(args[0]) if args else TOP
    if isinstance(a, IVal) and a.lo is not None and a.lo >= 0:
        return _j_bit(ast.RShift)(interp, args, kwargs, node, env, mod)
    return IVal(tile=True)


def _lax_select(interp, args, kwargs, node, env, mod):
    if len(args) == 3:
        return _as_ival(args[1]).join(_as_ival(args[2]))
    return IVal(tile=True)


def _pl_pallas_call(interp, args, kwargs, node, env, mod):
    from . import pallas_checks
    kernel = args[0] if args else None
    pv = PallasVal(
        kernel if isinstance(kernel, FuncVal) else None,
        kwargs.get("grid"), kwargs.get("in_specs"), kwargs.get("out_specs"),
        kwargs.get("out_shape"), node)
    pallas_checks.check_pallas_static(interp, pv, mod)
    return pv


def _pl_blockspec(interp, args, kwargs, node, env, mod):
    block = args[0] if args else kwargs.get("block_shape")
    index_map = args[1] if len(args) > 1 else kwargs.get("index_map")
    return BlockSpecVal(_shape_from_value(block) if block is not None else None,
                        index_map if isinstance(index_map, FuncVal) else None)


def _jax_struct(interp, args, kwargs, node, env, mod):
    shape = args[0] if args else kwargs.get("shape")
    dtype = args[1] if len(args) > 1 else kwargs.get("dtype")
    return StructVal(_shape_from_value(shape) if shape is not None else None,
                     _dtype_from(dtype))


_JNP_MODELS = {
    "where": _j_where, "minimum": _j_minimum, "maximum": _j_maximum,
    "zeros": _j_zeros(0), "ones": _j_zeros(1), "full": _j_zeros(None),
    "empty": _j_zeros(0), "zeros_like": _j_like(0), "ones_like": _j_like(1),
    "full_like": _j_like(None), "empty_like": _j_like(0),
    "arange": _j_arange, "pad": _j_pad, "reshape": _j_reshape,
    "concatenate": _j_concatenate, "stack": _j_stack, "vstack": _j_stack,
    "hstack": _j_stack, "transpose": _j_transpose, "swapaxes": _j_swapaxes,
    "matmul": _j_matmul, "dot": _j_matmul, "asarray": _j_asarray,
    "array": _j_asarray, "sum": _j_reduce, "prod": _j_reduce,
    "min": _j_reduce_minmax, "max": _j_reduce_minmax, "abs": _b_abs,
    "mod": _j_bit(ast.Mod), "remainder": _j_bit(ast.Mod),
    "left_shift": _j_bit(ast.LShift), "right_shift": _j_bit(ast.RShift),
    "bitwise_and": _j_bit(ast.BitAnd), "bitwise_or": _j_bit(ast.BitOr),
    "bitwise_xor": _j_bit(ast.BitXor), "uint32": None, "int32": None,
}

_ROOT_MODELS = {
    ("jax", "jit"): _jax_jit, ("jax", "vmap"): _jax_vmap,
    ("jax", "ShapeDtypeStruct"): _jax_struct,
    ("lax", "cond"): _lax_cond, ("lax", "select"): _lax_select,
    ("lax", "rem"): _lax_rem,
    # the elementwise lax primitives kernel bodies use in place of jnp
    # operators (trace time): the same interval models as the operators
    ("lax", "add"): _j_bit(ast.Add), ("lax", "sub"): _j_bit(ast.Sub),
    ("lax", "mul"): _j_bit(ast.Mult), ("lax", "shift_left"): _j_bit(ast.LShift),
    ("lax", "shift_right_logical"): _lax_shr_logical,
    ("lax", "bitwise_and"): _j_bit(ast.BitAnd),
    ("lax", "bitwise_or"): _j_bit(ast.BitOr),
    ("lax", "bitwise_xor"): _j_bit(ast.BitXor),
    ("lax", "min"): _j_minimum, ("lax", "max"): _j_maximum,
    ("lax", "dot_general"): _j_dot_general,
    ("pl", "pallas_call"): _pl_pallas_call, ("pl", "BlockSpec"): _pl_blockspec,
    ("functools", "reduce"): None,
}


def dispatch(interp, func: BuiltinVal, args, kwargs, node, env, mod):
    root, attr = func.root, func.attr
    if root == "builtins":
        model = _BUILTIN_MODELS.get(attr)
        if model is not None:
            return model(interp, args, kwargs, node, env, mod)
        if attr in ("float", "str", "repr", "print", "round", "id", "type",
                    "getattr", "divmod", "set", "dict"):
            return TOP
        return IVal(tile=_tile_of(*args))
    if root == "functools" and attr == "partial":
        if args and isinstance(args[0], (FuncVal, BuiltinVal)):
            target = args[0]
            if isinstance(target, FuncVal):
                return FuncVal(target.node, target.module, target.closure,
                               target.bound_args + tuple(args[1:]),
                               {**target.bound_kwargs, **kwargs},
                               target.jitted, target.donate)
            # functools.partial(jax.jit, ...) used as a decorator factory
            return target
        return TOP
    if root in ("jnp", "np"):
        from .absdom import INT_DTYPES as _ID
        if attr in _ID or attr in ("bfloat16", "float16", "float32", "float64"):
            return cast(args[0] if args else TOP, attr)
        model = _JNP_MODELS.get(attr)
        if model is not None:
            return model(interp, args, kwargs, node, env, mod)
        return IVal(tile=_tile_of(*args) or root == "jnp")
    model = _ROOT_MODELS.get((root, attr))
    if model is not None:
        return model(interp, args, kwargs, node, env, mod)
    if root == "lax":
        return IVal(tile=True)
    return IVal(tile=_tile_of(*args))


# -- bound methods ------------------------------------------------------------


def method(interp, bm: BoundMethod, args, kwargs, node, env, mod):
    base, attr = bm.base, bm.attr
    if isinstance(base, LVal):
        if attr == "append":
            if base.concrete and len(base.elems) < 4096:
                base.elems.append(args[0] if args else TOP)
            else:
                from .interp import _join_values
                cur = base.join_elem()
                item = args[0] if args else TOP
                base.elems = None
                base.elem = item if cur is None else _join_values(cur, item)
                base.length = IVal(0, None)
            return ConstVal(None)
        if attr == "extend" and args:
            mode, data = interp._iter_values(args[0])
            if base.concrete and mode == "concrete" and \
                    len(base.elems) + len(data) <= 4096:
                base.elems.extend(data)
            else:
                from .interp import _join_values
                other = (args[0].join_elem() if isinstance(args[0], LVal)
                         else TOP)
                cur = base.join_elem()
                if cur is None:
                    base.elem = other
                elif other is None:
                    base.elem = cur
                else:
                    base.elem = _join_values(cur, other)
                base.elems = None
                base.length = IVal(0, None)
            return ConstVal(None)
        if attr == "pop":
            if base.concrete and base.elems:
                return base.elems.pop()
            from .interp import elem_or_top
            return elem_or_top(base)
        if attr == "copy":
            return LVal(list(base.elems)) if base.concrete else base
        return TOP
    if isinstance(base, IVal):
        if attr == "astype":
            dt = _dtype_from(args[0] if args else kwargs.get("dtype"))
            return cast(base, dt) if dt else dataclasses.replace(base, dtype=None)
        if attr == "reshape":
            return _j_reshape(interp, [base, *args], kwargs, node, env, mod)
        if attr == "transpose":
            a = args[0] if len(args) == 1 else (TVal(tuple(args)) if args else None)
            return _j_transpose(interp, [base, a] if a is not None else [base],
                                kwargs, node, env, mod)
        if attr == "swapaxes":
            return _j_swapaxes(interp, [base, *args], kwargs, node, env, mod)
        if attr in ("sum", "prod", "mean", "dot"):
            return IVal(tile=base.tile)
        if attr in ("min", "max"):
            return dataclasses.replace(base, shape=None)
        if attr in ("item", "tolist"):
            return IVal.range(base.lo, base.hi)  # host value
        if attr in ("copy", "block_until_ready", "squeeze", "ravel", "flatten"):
            return dataclasses.replace(base, shape=None)
        if attr == "bit_length":
            return IVal(0, None)
        return IVal(tile=base.tile)
    return TOP
