"""Device-dispatch discipline: the fused window path stays one launch, one
transfer, one fetch per window — statically.

PR 8 made the packed single-dispatch GCM program the production transform
path and PR 9 sharded it; the invariant that makes those PRs worth their
complexity — ONE device dispatch per window, nothing materializing device
values mid-pipeline — is enforced today only by the runtime counters that
``make transform-demo``/``multichip-demo`` assert. A hidden ``np.asarray``
or ``block_until_ready`` added anywhere on the hot path serializes the
double-buffered pipeline and pays the per-launch floor again
*silently* until the next bench round. This checker closes
that gap at the AST level:

1. **Closure.** The static call closure of the hot window path — from
   ``TpuTransformBackend.transform_windows`` /
   ``_encrypt_dispatch``/``_decrypt_batch`` through
   ``_stage_packed``/``_launch_packed`` into the ``ops/gcm.py`` packed
   entry points and the kernel modules they call — resolved through
   imports, ``self`` methods, and module functions, restricted to
   ``HOT_PATH_MODULES`` (the codec paths have their own disciplines).

2. **Materialization/sync.** Inside the closure: ``block_until_ready`` and
   ``jax.device_get`` are findings anywhere; ``np.asarray``/``np.array``/
   ``float()``/``int()``/``bool()``/``.item()``/``.tobytes()`` are
   findings when their operand is *device-tainted* (assigned from a launch
   / staging / ``jnp.*`` producer — host-side packing of numpy buffers is
   the point of the path and stays legal). The sanctioned finish set
   (``SANCTIONED_MATERIALIZERS``: ``_encrypt_finish`` and peers, each with
   its justification) is where the window's ONE materialization lives.

3. **Retrace hazards.** A ``jax.jit`` call outside the vetted wrapper
   (``_packed_jit``, which lru-caches per shape family), or a bypass of
   the context caches (direct ``GcmContext``/``GcmVarlenContext``
   construction or ``_*context_cached`` calls outside ``ops/gcm.py``)
   whose shapes therefore do not flow through ``bucket_max_bytes``'s
   ladder, is a finding: an unbucketed shape recompiles the whole window
   program per distinct size (round-1 VERDICT weak 2).

4. **Donation.** The staged buffer is donated to XLA as the output
   allocation; touching it after the launch reads freed memory. Any load
   of a name passed as the donated operand (``donate=True`` packed calls,
   or ``_launch_packed`` which donates internally) on a later line of the
   same function is a finding — ``.is_deleted()`` excepted (it is the
   donation *probe*).

5. **Inter-stage materialization inside the fused closure** (ISSUE 13).
   A second closure is built from the TRACE-scope roots — the packed
   window impls that run under ``_packed_jit`` — where every non-static
   parameter is a tracer by construction. Inside it, any host
   materializer or sync on a traced value (``interstage:...`` findings)
   splits the one-program window into multiple programs, and any
   staged matmul reduction loop outside the sanctioned ladder fallback
   (``interstage:staged-ladder``) reintroduces the per-level HBM round
   trips the fused GHASH tree kernel exists to remove. The runtime
   counterpart is ``ops.gcm.planned_hbm_roundtrips`` /
   ``DispatchStats.hbm_roundtrips_per_window``, CI-gated <= 1 by
   ``make transform-demo``.

Like the other whole-project checkers this is an over-approximation with
explicit limits: taint does not flow through containers or across calls,
and lexical line order stands in for execution order. The runtime
counters (``DispatchStats``, ``ops.gcm.device_dispatches``) remain the
ground truth the demos assert; this pass catches the regression at review
time instead of the next bench round.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Optional

from tieredstorage_tpu.analysis import lockorder
from tieredstorage_tpu.analysis.core import Finding, Project

#: Entry points of the hot window path (summary keys). The device hot-cache
#: roots (ISSUE 12) cover the serve side: a resident decrypt buffer must be
#: SLICED device-side, never materialized mid-serve — a hidden np.asarray
#: on the hot serve path would turn every "free" hit into a device->host
#: fetch and is a static finding here.
HOT_PATH_ROOTS = (
    "tieredstorage_tpu/transform/tpu.py:TpuTransformBackend.transform_windows",
    "tieredstorage_tpu/transform/tpu.py:TpuTransformBackend._encrypt_dispatch",
    "tieredstorage_tpu/transform/tpu.py:TpuTransformBackend._decrypt_batch",
    "tieredstorage_tpu/ops/gcm.py:gcm_window_packed",
    "tieredstorage_tpu/ops/gcm.py:gcm_varlen_window_packed",
    "tieredstorage_tpu/ops/gcm.py:gcm_keyed_window_packed",
    "tieredstorage_tpu/fetch/cache/device_hot.py:DeviceHotCache.get_chunks",
    "tieredstorage_tpu/fetch/cache/device_hot.py:DeviceHotCache.device_rows",
    # The cross-request batcher (ISSUE 15) is the decrypt hot path under
    # concurrency: a hidden materialization in submit or the merged flush
    # would pay once per COALESCED launch and stall every waiter at once.
    "tieredstorage_tpu/transform/batcher.py:WindowBatcher.submit",
    "tieredstorage_tpu/transform/batcher.py:WindowBatcher._flush_group",
    # The work-class scheduler half (ISSUE 16): the encrypt submit path is
    # the produce hot path under concurrency, same bar as submit.
    "tieredstorage_tpu/transform/batcher.py:WindowBatcher.submit_encrypt",
)

#: Modules the closure may traverse: the window path and the kernel stack
#: under it. The compression codecs (thuff/lzhuff/zstd) materialize on
#: their own schedules and are checked by their own demos.
HOT_PATH_MODULES = (
    "tieredstorage_tpu/transform/tpu.py",
    "tieredstorage_tpu/ops/gcm.py",
    "tieredstorage_tpu/ops/gf128.py",
    "tieredstorage_tpu/ops/aes.py",
    "tieredstorage_tpu/ops/aes_bitsliced.py",
    "tieredstorage_tpu/ops/aes_pallas.py",
    "tieredstorage_tpu/ops/ghash_pallas.py",
    "tieredstorage_tpu/parallel/mesh.py",
    "tieredstorage_tpu/fetch/cache/device_hot.py",
    "tieredstorage_tpu/transform/batcher.py",
    "tieredstorage_tpu/transform/scheduler.py",
)

#: Functions allowed to materialize device values, with the reason. This is
#: the "finish set": burn entries down, never add one without a sentence.
SANCTIONED_MATERIALIZERS = {
    "tieredstorage_tpu/transform/tpu.py:TpuTransformBackend._encrypt_finish":
        "the window's ONE device->host fetch: blocks on the oldest staged "
        "window after pipeline_depth newer ones were dispatched",
    "tieredstorage_tpu/transform/tpu.py:TpuTransformBackend._decrypt_window":
        "decrypt finish half: one fetch of plaintext+expected tags, "
        "verified host-side (the launch half is still checked upstream)",
    "tieredstorage_tpu/ops/aes_bitsliced.py:_forced_crosscheck_ok":
        "one-time forced-Pallas output cross-check at first use, memoized",
    "tieredstorage_tpu/transform/batcher.py:WindowBatcher._collect":
        "a merged flush's waiter fetching its own rows on its own thread, "
        "with per-row tag verification (the batched counterpart of "
        "_decrypt_window's finish half); the flusher itself never fetches",
}

#: Vetted jit wrappers: every shape family they compile is bounded (the
#: packed wrapper is lru_cached and its static shapes come from the
#: bucketed contexts).
SANCTIONED_JIT_WRAPPERS = {
    "tieredstorage_tpu/ops/gcm.py:_packed_jit",
    # The merged window of several keys: lru-cached per donation, its
    # static shapes the bucketed varlen contexts' and the row ladder's.
    "tieredstorage_tpu/ops/gcm.py:_keyed_jit",
}

#: Roots of the TRACE-scope closure (ISSUE 13): the packed window impls
#: that run under `_packed_jit`. Everything they reach executes inside ONE
#: traced program — the fused-window closure the tree kernel keeps to a
#: single stage.
TRACE_CLOSURE_ROOTS = (
    "tieredstorage_tpu/ops/gcm.py:_packed_fixed_impl",
    "tieredstorage_tpu/ops/gcm.py:_packed_varlen_impl",
    "tieredstorage_tpu/ops/gcm.py:_packed_keyed_impl",
)

#: Trace-scope parameters that carry static Python values (jit
#: static_argnames and host ints threaded through) — every OTHER parameter
#: of a trace-scope function is a tracer by construction.
TRACE_STATIC_PARAMS = {
    "self", "chunk_bytes", "n_blocks", "decrypt", "max_bytes", "m_max",
    "m_a", "m_cap", "aad_bit_len", "first_counter", "interpret",
}

#: Trace-scope functions allowed to contain a staged matmul-reduction loop,
#: with the reason. Burn down, never add without a sentence.
SANCTIONED_STAGED_REDUCERS = {
    "tieredstorage_tpu/ops/gcm.py:_ghash_keyed":
        "a keyed merged window's levels above the first: per-row batched "
        "matmuls of [B, G, k*128] node bits under each row's own key's "
        "operand (no tree kernel takes per-row operands); counted by "
        "planned_keyed_hbm_roundtrips",
    "tieredstorage_tpu/ops/gcm.py:_ghash_grouped":
        "the XLA grouped-power ladder is the TESTED FALLBACK when the "
        "fused GHASH tree kernel cannot engage (no Mosaic on this "
        "platform, single-level shapes); its per-level HBM round trips "
        "are counted honestly by planned_hbm_roundtrips and gated by "
        "make transform-demo",
}

#: Calls that produce (or carry) device values: assignment from one taints
#: the bound name for the rest of the function.
DEVICE_PRODUCER_NAMES = {
    "gcm_window_packed", "gcm_varlen_window_packed",
    "gcm_keyed_window_packed", "take_rows", "ctr_keystream_keyed",
    "aes_encrypt_planes_keyed_pallas", "ghash_level1_keyed_pallas",
    "gcm_encrypt_chunks", "gcm_decrypt_chunks",
    "gcm_encrypt_varlen", "gcm_decrypt_varlen", "_run_varlen",
    "_launch_packed", "_stage_packed", "_encrypt_dispatch",
    # ISSUE 16 seam: the batcher-aware encrypt dispatch returns either a
    # staged device tuple or an _EncryptHandle wrapping one — tainted
    # either way.
    "_dispatch_encrypt_window",
    "_gcm_process_batch", "_gcm_varlen_batch",
    "aes_encrypt_blocks", "ctr_keystream_batch",
    "aes_encrypt_planes_pallas", "ghash_level1_pallas",
    "device_put", "shard",
    # Device hot-cache tier: retained decrypt rows stay device values.
    "device_rows", "offer_decrypt_window",
}
DEVICE_PRODUCER_PREFIXES = ("jnp.", "jax.numpy.", "jax.device_put")

#: Parameters conventionally carrying staged device buffers.
DEVICE_PARAM_NAMES = {"staged", "data_packed"}

#: Materializers that are findings only on device-tainted operands.
MATERIALIZE_CALL_NAMES = {"np.asarray", "np.array", "np.copy", "numpy.asarray",
                          "numpy.array", "float", "int", "bool"}
MATERIALIZE_ATTRS = {"item", "tobytes"}
#: Sync calls that are findings on ANY operand inside the closure.
SYNC_ATTRS = {"block_until_ready"}
SYNC_CALL_NAMES = {"jax.device_get", "jax.block_until_ready"}

#: Attribute reads of a donated buffer that are still legal.
ALLOWED_AFTER_DONATE = {"is_deleted"}

#: Donating calls -> positional index of the donated operand.
_DONATING_CALLS = {
    "gcm_window_packed": 2,
    "gcm_varlen_window_packed": 2,
    "_launch_packed": 1,  # self._launch_packed(ctx, staged, ...)
}


# ---------------------------------------------------------------- closure
@dataclasses.dataclass
class _Fn:
    key: str
    rel_path: str
    qualname: str
    node: ast.FunctionDef
    fm: object
    class_name: Optional[str]


def _module_index(file_models: dict) -> dict[str, str]:
    return {fm.module_name: rel for rel, fm in file_models.items()}


def _resolve_call(func: ast.AST, fn: _Fn, modules: dict[str, str]) -> Optional[str]:
    """Summary key for a call target: local/module functions, imported
    module functions (``from x import f`` and ``import x as y; y.f()``),
    and ``self`` methods."""
    fm = fn.fm
    if isinstance(func, ast.Name):
        if func.id in fm.functions:
            return f"{fn.rel_path}:{func.id}"
        dotted = fm.imports.get(func.id)
        if dotted and "." in dotted:
            mod, _, name = dotted.rpartition(".")
            rel = modules.get(mod)
            if rel is not None:
                return f"{rel}:{name}"
        return None
    if not isinstance(func, ast.Attribute):
        return None
    recv, meth = func.value, func.attr
    if isinstance(recv, ast.Name) and recv.id == "self" and fn.class_name:
        cm = fm.classes.get(fn.class_name)
        if cm is not None and meth in cm.methods:
            return f"{fn.rel_path}:{fn.class_name}.{meth}"
        return None
    dotted = lockorder._dotted(func)
    if dotted and "." in dotted:
        head, _, rest = dotted.partition(".")
        base = fm.imports.get(head)
        if base:
            full = f"{base}.{rest}"
            mod, _, name = full.rpartition(".")
            rel = modules.get(mod)
            if rel is not None:
                return f"{rel}:{name}"
    return None


def build_closure(project: Project, roots=HOT_PATH_ROOTS, stop_at=()):
    """(closure functions by key, file models, module index) — exposed for
    tests and the docs. `roots` selects the entry set: the hot window path
    (default) or TRACE_CLOSURE_ROOTS for the fused trace scope. Functions
    in `stop_at` are kept in the closure but their callees are not
    traversed (the sanctioned host-gate subtrees of the trace scope run
    eagerly at trace time, not inside the program)."""
    file_models = {
        pf.rel_path: lockorder._build_file_model(pf)
        for pf in project.files
        if pf.rel_path in HOT_PATH_MODULES
    }
    modules = _module_index(file_models)
    fns: dict[str, _Fn] = {}
    for rel, fm in file_models.items():
        for name, node in fm.functions.items():
            fns[f"{rel}:{name}"] = _Fn(
                key=f"{rel}:{name}", rel_path=rel, qualname=name,
                node=node, fm=fm, class_name=None,
            )
        for cls_name, cm in fm.classes.items():
            for m, node in cm.methods.items():
                key = f"{rel}:{cls_name}.{m}"
                fns[key] = _Fn(
                    key=key, rel_path=rel, qualname=f"{cls_name}.{m}",
                    node=node, fm=fm, class_name=cls_name,
                )

    closure: dict[str, _Fn] = {}
    stack = [k for k in roots if k in fns]
    while stack:
        key = stack.pop()
        if key in closure:
            continue
        fn = fns.get(key)
        if fn is None:
            continue
        closure[key] = fn
        if key in stop_at:
            continue
        for node in ast.walk(fn.node):
            if isinstance(node, ast.Call):
                callee = _resolve_call(node.func, fn, modules)
                if callee is not None and callee not in closure:
                    stack.append(callee)
    return closure, file_models, modules


# ------------------------------------------------------------------ scans
def _call_name(func: ast.AST) -> Optional[str]:
    return lockorder._dotted(func)


def _tainted_names(fn: _Fn) -> set[str]:
    """Names bound (directly or via tuple unpack) from device producers,
    plus conventionally named device parameters. Two passes so a name
    assigned from another tainted name late in the function still taints
    earlier reported uses conservatively (propagation shared with the
    trace-scope scan, `_propagate_taint`)."""
    tainted: set[str] = {
        a.arg for a in fn.node.args.args if a.arg in DEVICE_PARAM_NAMES
    }
    return _propagate_taint(fn, tainted)


def _scan_materialization(fn: _Fn, findings: list[Finding]) -> None:
    if fn.key in SANCTIONED_MATERIALIZERS:
        return
    tainted = _tainted_names(fn)

    def arg_tainted(call: ast.Call) -> bool:
        for a in list(call.args) + [kw.value for kw in call.keywords]:
            for node in ast.walk(a):
                if isinstance(node, ast.Name) and node.id in tainted:
                    return True
                if isinstance(node, ast.Call):
                    name = _call_name(node.func)
                    if name and (
                        name.split(".")[-1] in DEVICE_PRODUCER_NAMES
                        or name.startswith(DEVICE_PRODUCER_PREFIXES)
                    ):
                        return True
        return False

    for node in ast.walk(fn.node):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        name = _call_name(func)
        if isinstance(func, ast.Attribute) and func.attr in SYNC_ATTRS:
            findings.append(Finding(
                checker="device-dispatch",
                path=fn.rel_path, line=node.lineno, qualname=fn.qualname,
                detail=f"sync:{func.attr}",
                message=(
                    f"{func.attr}() inside the fused-window closure "
                    "serializes the double-buffered pipeline (every launch "
                    "re-pays the ~62 ms floor); only _encrypt_finish may "
                    "block, on the window's single packed buffer"
                ),
            ))
            continue
        if name in SYNC_CALL_NAMES:
            findings.append(Finding(
                checker="device-dispatch",
                path=fn.rel_path, line=node.lineno, qualname=fn.qualname,
                detail=f"sync:{name}",
                message=(
                    f"{name}() inside the fused-window closure forces a "
                    "device->host sync mid-pipeline; materialize only in "
                    "the sanctioned finish set"
                ),
            ))
            continue
        is_materializer = name in MATERIALIZE_CALL_NAMES or (
            isinstance(func, ast.Attribute) and func.attr in MATERIALIZE_ATTRS
        )
        if not is_materializer:
            continue
        receiver_tainted = (
            isinstance(func, ast.Attribute)
            and isinstance(func.value, ast.Name)
            and func.value.id in tainted
        )
        if receiver_tainted or arg_tainted(node):
            label = name or func.attr
            findings.append(Finding(
                checker="device-dispatch",
                path=fn.rel_path, line=node.lineno, qualname=fn.qualname,
                detail=f"materialize:{label.split('.')[-1]}",
                message=(
                    f"{label}() materializes a device value inside the "
                    "fused-window closure (outside the sanctioned finish "
                    "set): the hidden sync stalls the pipeline and "
                    "reintroduces the per-launch floor; keep the value on "
                    "device or move the fetch into _encrypt_finish"
                ),
            ))


def _scan_retrace(fn: _Fn, findings: list[Finding]) -> None:
    for node in ast.walk(fn.node):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node.func)
        if name is None:
            continue
        last = name.split(".")[-1]
        if (
            (name in ("jax.jit", "jit") or last == "jit")
            and fn.key not in SANCTIONED_JIT_WRAPPERS
        ):
            findings.append(Finding(
                checker="device-dispatch",
                path=fn.rel_path, line=node.lineno, qualname=fn.qualname,
                detail="unvetted-jit",
                message=(
                    "jax.jit call outside the vetted _packed_jit wrapper: "
                    "without the lru-cached wrapper + bucketed static "
                    "shapes every distinct window shape recompiles the "
                    "program (multi-second XLA compile per window)"
                ),
            ))
        elif (
            last in ("GcmContext", "GcmVarlenContext",
                     "_build_context", "_build_varlen_context")
            and fn.rel_path != "tieredstorage_tpu/ops/gcm.py"
        ):
            findings.append(Finding(
                checker="device-dispatch",
                path=fn.rel_path, line=node.lineno, qualname=fn.qualname,
                detail=f"shape-not-bucketed:{last}",
                message=(
                    f"{last} constructed outside ops/gcm.py bypasses "
                    "make_context/make_varlen_context, so the window shape "
                    "does not flow through bucket_max_bytes's ladder - a "
                    "retrace hazard (one XLA compile per distinct "
                    "compressed size)"
                ),
            ))


def _scan_donation(fn: _Fn, findings: list[Finding]) -> None:
    donated: list[tuple[str, int]] = []  # (name, last line of the donating call)
    in_donating_call: set[int] = set()   # id() of Name nodes inside one
    for node in ast.walk(fn.node):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node.func)
        last = name.split(".")[-1] if name else None
        if last not in _DONATING_CALLS:
            continue
        if last != "_launch_packed" and not any(
            kw.arg == "donate"
            and isinstance(kw.value, ast.Constant)
            and kw.value.value is True
            for kw in node.keywords
        ):
            continue
        # A later donating call (the fixed/varlen sibling branch) passing
        # the same buffer is not a use-after-donate: only one branch runs.
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name):
                in_donating_call.add(id(sub))
        idx = _DONATING_CALLS[last]
        if idx < len(node.args) and isinstance(node.args[idx], ast.Name):
            donated.append((node.args[idx].id, node.end_lineno or node.lineno))
    if not donated:
        return
    seen_fp: set[str] = set()
    for node in ast.walk(fn.node):
        if not (isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load)):
            continue
        if id(node) in in_donating_call:
            continue
        parent = getattr(node, "_ts_parent", None)
        if (
            isinstance(parent, ast.Attribute)
            and parent.attr in ALLOWED_AFTER_DONATE
        ):
            continue
        for dname, dline in donated:
            if node.id == dname and node.lineno > dline:
                f = Finding(
                    checker="device-dispatch",
                    path=fn.rel_path, line=node.lineno, qualname=fn.qualname,
                    detail=f"use-after-donate:{dname}",
                    message=(
                        f"{dname!r} was donated to XLA as the launch's "
                        "output allocation and is deleted after dispatch; "
                        "reading it here is use-after-free (only "
                        ".is_deleted() is legal - it is the donation "
                        "probe)"
                    ),
                )
                if f.fingerprint not in seen_fp:
                    seen_fp.add(f.fingerprint)
                    findings.append(f)


# ----------------------------------------------- fused trace scope (rule 5)
def _trace_tainted_names(fn: _Fn) -> set[str]:
    """Traced-value names inside a trace-scope function: every parameter
    that is not a known static is a tracer by construction (the function
    runs under `_packed_jit`), then the same producer/assignment
    propagation as `_tainted_names`."""
    args = fn.node.args
    params = list(getattr(args, "posonlyargs", [])) + list(args.args) + list(
        args.kwonlyargs
    )
    tainted = {a.arg for a in params if a.arg not in TRACE_STATIC_PARAMS}
    return _propagate_taint(fn, tainted)


def _propagate_taint(fn: _Fn, tainted: set[str]) -> set[str]:
    """Two-pass producer/assignment taint propagation shared by the hot
    and trace closures (extracted from `_tainted_names`)."""

    def is_producer(call: ast.Call) -> bool:
        name = _call_name(call.func)
        if name is None:
            return False
        if name.split(".")[-1] in DEVICE_PRODUCER_NAMES:
            return True
        return name.startswith(DEVICE_PRODUCER_PREFIXES)

    def expr_tainted(expr: ast.AST) -> bool:
        for node in ast.walk(expr):
            if isinstance(node, ast.Name) and node.id in tainted:
                return True
            if isinstance(node, ast.Call) and is_producer(node):
                return True
        return False

    for _ in range(2):
        for node in ast.walk(fn.node):
            if isinstance(node, ast.Assign) and expr_tainted(node.value):
                for target in node.targets:
                    elts = target.elts if isinstance(target, ast.Tuple) else [target]
                    for t in elts:
                        if isinstance(t, ast.Name):
                            tainted.add(t.id)
    return tainted


def _scan_interstage(fn: _Fn, findings: list[Finding]) -> None:
    """Host materializers/syncs inside the TRACED fused closure. Every
    value here is a tracer, so a materialization cannot be a cheap host
    peek: it cuts the one-program window into multiple programs with an
    HBM round trip (and a host sync) at the cut. The sanctioned set is
    the trace-time host gates (memoized preflight cross-checks, run
    outside the trace)."""
    if fn.key in SANCTIONED_MATERIALIZERS:
        return
    tainted = _trace_tainted_names(fn)
    for node in ast.walk(fn.node):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        name = _call_name(func)
        is_sync = (
            isinstance(func, ast.Attribute) and func.attr in SYNC_ATTRS
        ) or name in SYNC_CALL_NAMES
        if is_sync:
            findings.append(Finding(
                checker="device-dispatch",
                path=fn.rel_path, line=node.lineno, qualname=fn.qualname,
                detail=f"interstage:sync:{(name or func.attr).split('.')[-1]}",
                message=(
                    "device sync inside the TRACED fused-window closure: "
                    "the window must stay one device program "
                    "(hbm_roundtrips_per_window <= 1); move host work "
                    "outside the packed impls"
                ),
            ))
            continue
        is_materializer = name in MATERIALIZE_CALL_NAMES or (
            isinstance(func, ast.Attribute) and func.attr in MATERIALIZE_ATTRS
        )
        if not is_materializer:
            continue
        receiver_tainted = (
            isinstance(func, ast.Attribute)
            and isinstance(func.value, ast.Name)
            and func.value.id in tainted
        )
        operand_tainted = any(
            isinstance(sub, ast.Name) and sub.id in tainted
            for a in list(node.args) + [kw.value for kw in node.keywords]
            for sub in ast.walk(a)
        )
        if receiver_tainted or operand_tainted:
            label = (name or func.attr).split(".")[-1]
            findings.append(Finding(
                checker="device-dispatch",
                path=fn.rel_path, line=node.lineno, qualname=fn.qualname,
                detail=f"interstage:materialize:{label}",
                message=(
                    f"{label}() materializes a traced value inside the "
                    "fused-window closure: XLA must cut the one-program "
                    "window here and round-trip the intermediate through "
                    "HBM — exactly the inter-stage materialization the "
                    "fused GHASH tree kernel removes (ISSUE 13)"
                ),
            ))


#: Calls that stage a matmul reduction level (HBM materialization of the
#: per-level node tensor between them when looped).
_MATMUL_NAMES = {"dot_general", "dot", "matmul", "einsum", "tensordot"}


def _scan_staged_reduction(fn: _Fn, findings: list[Finding]) -> None:
    """A matmul inside a loop in trace scope is a STAGED reduction: each
    iteration materializes its node tensor in HBM before the next
    contracts it — the grouped-power ladder shape. Only the sanctioned
    fallback (`_ghash_grouped`, counted by planned_hbm_roundtrips) may
    carry one; anywhere else it silently reintroduces the per-level round
    trips."""
    if fn.key in SANCTIONED_STAGED_REDUCERS:
        return
    for loop in ast.walk(fn.node):
        if not isinstance(loop, (ast.For, ast.While)):
            continue
        for sub in ast.walk(loop):
            if not isinstance(sub, ast.Call):
                continue
            name = _call_name(sub.func) or ""
            if name.split(".")[-1] in _MATMUL_NAMES:
                findings.append(Finding(
                    checker="device-dispatch",
                    path=fn.rel_path, line=sub.lineno, qualname=fn.qualname,
                    detail="interstage:staged-ladder",
                    message=(
                        "matmul-in-a-loop inside the traced fused closure "
                        "is a staged reduction (one HBM round trip per "
                        "level); the ladder lives only in the sanctioned "
                        "fallback — route the reduction through the fused "
                        "GHASH tree kernel instead"
                    ),
                ))
                break  # one finding per loop
    return


def check_device_dispatch(project: Project) -> list[Finding]:
    closure, _file_models, _modules = build_closure(project)
    findings: list[Finding] = []
    for key in sorted(closure):
        fn = closure[key]
        _scan_materialization(fn, findings)
        _scan_retrace(fn, findings)
        _scan_donation(fn, findings)
    trace_closure, _tfm, _tmod = build_closure(
        project, TRACE_CLOSURE_ROOTS,
        stop_at=frozenset(SANCTIONED_MATERIALIZERS),
    )
    for key in sorted(trace_closure):
        fn = trace_closure[key]
        _scan_interstage(fn, findings)
        _scan_staged_reduction(fn, findings)
    return findings
