"""Device path of the worker operators: one jitted program per task.

A task's per-row work — filter and compute expressions, broadcast and
partitioned equi-joins (sort-probe), partial aggregation, and the hash
partition into the §3.2 partition-major layout — runs as ONE jitted JAX
program on the task's chip (``on_chip``, the coordinator's task
placement; JAX's default device outside it). The host keeps what the
design puts there: the §3.2 object decode before the program and the
encode after it (the store is the only medium, so a task's bytes cross
the host at every GET and PUT), and the final merge and sort/limit over
the few-row partials (``relational.ops``).

The answers are those of ``relational.ops`` (the numpy reference that
``engine.oracle`` runs), row for row and byte for byte on the CPU:

* the splitmix64 hash is computed in uint64, bit-identical to
  ``ops._splitmix64``;
* every sort is stable, so partitions keep the input's row order, join
  output is probe-row-major with matches in stable build-key order, and
  groups come out in ``np.unique``'s lexicographic order;
* sums accumulate in float64 in row order, and each computed column gets
  the dtype numpy gives it.

64-bit types live only inside ``jax.enable_x64(True)``, a per-thread
scope, so the coordinator's executor threads may run tasks concurrently
and the model path keeps its 32-bit defaults.

Compiles are bounded: row counts are padded to power-of-two buckets and
carried with a validity mask — a filter narrows the mask and nothing is
compacted until the task's output. The data-dependent sizes (rows kept,
join matches, partition bounds) come back to the host once per task, in
one transfer; a join whose matches overflow its speculative capacity (the
probe side's bucket) is re-run with a larger one.
"""
from __future__ import annotations

import contextlib
import functools
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.obs import spans
from repro.relational.table import DictColumn, Table

MIN_BUCKET = 1024           # smallest padded row count
DIGIT_BITS = 4              # radix-sort digit: 16 counting-sort bins
TASK_EVENT = "/repro/device_ops/task"   # jax.monitoring event, per task

_BIN = {"add": "add", "sub": "subtract", "mul": "multiply",
        "lt": "less", "le": "less_equal", "gt": "greater",
        "ge": "greater_equal", "eq": "equal", "ne": "not_equal",
        "and": "logical_and", "or": "logical_or"}
_I64_MAX = np.iinfo(np.int64).max
_placed = threading.local()     # .device: the chip of this thread's tasks


@contextlib.contextmanager
def on_chip(device):
    """Run this thread's ``run`` calls inside the block on ``device``, the
    chip the coordinator placed the task on, and count their padded rows
    under its ``spans.rows_padded_chip`` counter. Outside such a block
    (or with None) ``run`` uses JAX's default device and records no
    per-chip counter."""
    prev = getattr(_placed, "device", None)
    _placed.device = device
    try:
        yield
    finally:
        _placed.device = prev


def bucket(n: int, floor: int = MIN_BUCKET) -> int:
    """Padded size for n rows: the next power of two, at least ``floor``."""
    return max(floor, 1 << max(n - 1, 0).bit_length())


# ---------------------------------------------------------------------------
# host side: schema pass (column order, dictionaries, resolved codes)
# ---------------------------------------------------------------------------

def _resolve(e, dicts: dict):
    """Replace ``{"code": [col, value]}`` by the value's code in col's
    dictionary (-1 when absent), so the device never sees strings."""
    if isinstance(e, dict):
        if "code" in e:
            col, val = e["code"]
            v = val.encode() if isinstance(val, str) else val
            vals = dicts[col]
            return {"code_id": vals.index(v) if v in vals else -1}
        if "fn" in e:
            return {"fn": e["fn"],
                    "args": [_resolve(a, dicts) for a in e["args"]]}
    return e


def _schema(ops: list, t: Table, builds: dict):
    """Walk the ops on the host: resolve dictionary codes and follow the
    column order and dictionaries the device program's output will have
    (its arrays come back as a name-sorted pytree)."""
    names = t.column_names()
    dicts = {n: c.values for n, c in t.cols.items()
             if isinstance(c, DictColumn)}
    resolved = []
    for op in ops:
        kind = op["op"]
        if kind == "filter":
            op = {**op, "pred": _resolve(op["pred"], dicts)}
        elif kind == "project":
            names = list(op["columns"])
        elif kind == "compute":
            op = {**op, "expr": _resolve(op["expr"], dicts)}
            names = list(dict.fromkeys(names + [op["name"]]))
            dicts.pop(op["name"], None)
        elif kind == "partial_agg":
            aggs = [[n, fn, None if e is None else _resolve(e, dicts)]
                    for n, fn, e in op["aggs"]]
            op = {**op, "aggs": aggs}
            outs = list(op["keys"])
            for n, fn, _ in aggs:
                outs += [n, n + "__count"] if fn == "avg" else [n]
            names = list(dict.fromkeys(outs))
            dicts = {k: dicts[k] for k in op["keys"] if k in dicts}
        elif kind in ("join", "broadcast_join"):
            build = builds[op["table"]]
            for n, c in build.cols.items():
                if n not in names:
                    names.append(n)
                if isinstance(c, DictColumn):
                    dicts[n] = c.values
                else:
                    dicts.pop(n, None)
        else:
            raise ValueError(kind)
        resolved.append(op)
        dicts = {n: v for n, v in dicts.items() if n in names}
    return resolved, names, dicts


def _to_device(t: Table, device=None):
    """(name -> padded column, row count), the columns put on ``device``
    (JAX's default device when None)."""
    n = len(t)
    cap = bucket(n)
    cols = {}
    for name, c in t.cols.items():
        a = np.asarray(c.codes if isinstance(c, DictColumn) else c)
        p = np.zeros(cap, a.dtype)
        p[:n] = a
        cols[name] = p
    return jax.device_put(cols, device), np.int32(n)


# ---------------------------------------------------------------------------
# device side (traced once per (program, bucket))
# ---------------------------------------------------------------------------

def _eval(cols: dict, e, xp):
    """The expression mini-language of ``relational.ops`` over ``xp``
    (jnp on the device; np on empty columns to find numpy's dtype)."""
    if isinstance(e, str):
        return cols[e]
    if isinstance(e, (int, float)):
        return e
    if "const" in e:
        return e["const"]
    if "code_id" in e:
        return np.int64(e["code_id"])
    fn = e["fn"]
    args = [_eval(cols, a, xp) for a in e["args"]]
    if fn == "one_minus":
        return 1.0 - args[0]
    if fn == "one_plus":
        return 1.0 + args[0]
    if fn == "not":
        return xp.logical_not(args[0])
    if fn == "in":
        m = xp.zeros(xp.shape(args[0]), bool)
        for v in args[1:]:
            m = m | xp.equal(args[0], v)
        return m
    return getattr(xp, _BIN[fn])(*args)


def _column(cols: dict, e, cap: int, dtype=None):
    """Evaluate e as a full column, in ``dtype`` or numpy's result dtype."""
    if dtype is None:
        empty = {n: np.empty(0, c.dtype) for n, c in cols.items()}
        dtype = np.asarray(_eval(empty, e, np)).dtype
    v = jnp.asarray(_eval(cols, e, jnp)).astype(dtype)
    return jnp.broadcast_to(v, (cap,))


def splitmix64(x):
    """Device twin of ``ops._splitmix64`` (uint64 arithmetic wraps)."""
    x = x.astype(jnp.uint64) + jnp.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> 27)) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> 31)


def _iota(n: int):
    return jnp.arange(n, dtype=jnp.int32)


def _cumsum(x):
    """Inclusive prefix sum along axis 0 of a power-of-two length, in two
    levels of about sqrt(n): a long 1-D scan compiles slowly on the TPU."""
    n = x.shape[0]
    assert n & (n - 1) == 0, n
    c = 1 << (n.bit_length() // 2)
    y = jnp.cumsum(x.reshape((n // c, c) + x.shape[1:]), axis=1)
    tot = y[:, -1]
    return (y + (jnp.cumsum(tot, axis=0) - tot)[:, None]).reshape(x.shape)


def _prefix_counts(oh):
    """Inclusive prefix sums down the rows of a 0/1 matrix [n, bins]:
    within blocks of 256 rows by a triangular matmul on the MXU, across
    blocks by a short cumsum. Exact: 0/1 products summed in float32."""
    n, bins = oh.shape
    c = min(n, 256)
    tri = jnp.tril(jnp.ones((c, c), jnp.float32))
    y = jnp.einsum("ij,rjk->rik", tri, oh.reshape(n // c, c, bins),
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    tot = y[:, -1]
    return (y + (jnp.cumsum(tot, axis=0) - tot)[:, None]).reshape(n, bins)


def _sort_pass(perm, digit):
    """One stable counting-sort pass: reorder ``perm`` by ``digit``, whose
    values lie in [0, 2**DIGIT_BITS)."""
    oh = digit[:, None] == _iota(1 << DIGIT_BITS)[None, :]
    incl = _prefix_counts(oh.astype(jnp.float32))   # [n, bins]
    counts = incl[-1]
    dest = (jnp.cumsum(counts) - counts)[digit] \
        + jnp.sum(jnp.where(oh, incl, 0), axis=1) - 1
    return jnp.zeros_like(perm).at[dest].set(perm, unique_indices=True)


def _order(valid, keys=()):
    """Stable permutation: valid rows first, sorted lexicographically by
    the integer keys, then the invalid rows in input order. An LSD radix
    sort of DIGIT_BITS-bit counting passes, as many per key as its span of
    values among valid rows needs: XLA's sort takes tens of seconds to
    compile for the TPU, these loops a few. Its ops sit in a
    ``radix_sort`` name scope, under the scope of the operator that sorts.
    """
    with jax.named_scope("radix_sort"):
        perm = _iota(valid.shape[0])
        for k in reversed(keys):
            # order-preserving int64 -> uint64, offset from the smallest key
            u = k.astype(jnp.int64).astype(jnp.uint64) ^ jnp.uint64(1 << 63)
            lo = jnp.min(jnp.where(valid, u,
                                   jnp.uint64(np.iinfo(np.uint64).max)))
            u = jnp.where(valid, u - lo, 0)
            bits = 64 - lax.clz(jnp.max(u)).astype(jnp.int32)

            def body(i, perm, u=u):
                shift = (i * DIGIT_BITS).astype(jnp.uint64)
                return _sort_pass(perm, ((u[perm] >> shift)
                                         & (2 ** DIGIT_BITS - 1)).astype(
                                             jnp.int32))
            perm = lax.fori_loop(0, (bits + DIGIT_BITS - 1) // DIGIT_BITS,
                                 body, perm)
        return _sort_pass(perm, (~valid[perm]).astype(jnp.int32))


def _join(cols, mask, bcols, bn, lkey, rkey, cap_out):
    """Inner equi-join, any multiplicity: stable-sort the build keys,
    probe with searchsorted, expand matches into ``cap_out`` rows."""
    cap = mask.shape[0]
    bcap = next(iter(bcols.values())).shape[0]
    bvalid = _iota(bcap) < bn
    rk = bcols[rkey].astype(jnp.int64)
    order = _order(bvalid, [rk])
    # monotone over the whole array; matches are clipped to the bn valid
    rks = jnp.where(bvalid[order], rk[order], _I64_MAX)
    lk = cols[lkey].astype(jnp.int64)
    lo = jnp.minimum(jnp.searchsorted(rks, lk, side="left"), bn)
    hi = jnp.minimum(jnp.searchsorted(rks, lk, side="right"), bn)
    counts = jnp.where(mask, hi - lo, 0).astype(jnp.int64)
    ends = _cumsum(counts)
    total = ends[-1]
    j = jnp.arange(cap_out, dtype=jnp.int64)
    l_idx = jnp.minimum(jnp.searchsorted(ends, j, side="right"), cap - 1)
    within = j - (ends[l_idx] - counts[l_idx])
    r_idx = order[jnp.clip(lo[l_idx] + within, 0, bcap - 1)]
    out = {n: c[l_idx] for n, c in cols.items()}
    for n, c in bcols.items():
        out[n] = c[r_idx]               # right overwrites left (ops.op_join)
    return out, j < total, total


_COMBINER = {"sum": "add", "avg": "add", "count": "add", "min": "min",
             "max": "max"}
_INIT = {"add": 0.0, "min": np.inf, "max": -np.inf}


def _segments(aggs):
    """The float64 value columns of a partial aggregate by combiner:
    {combiner: ([expression, None for ones], [(output name, column)])}.
    A combiner builds each distinct expression once: count and an avg's
    ``__count`` share ones, sum and avg of one expression share it."""
    by = {}
    for name, fn, expr in aggs:
        if fn not in _COMBINER:
            raise ValueError(fn)
        parts = [(name, None if fn == "count" else expr)]
        if fn == "avg":
            parts.append((name + "__count", None))
        exprs, outs = by.setdefault(_COMBINER[fn], ([], []))
        for out, e in parts:
            if e not in exprs:
                exprs.append(e)
            outs.append((out, exprs.index(e)))
    return by


def _aggregate(cols, mask, keys, aggs):
    """Partial aggregate; groups in np.unique order (lexicographic int64
    keys), sums/counts/min/max in float64 accumulated in row order. One
    segment scatter per combiner, over all of its value columns, in a
    ``segment`` name scope."""
    cap = mask.shape[0]
    if keys:
        kv = [cols[k].astype(jnp.int64) for k in keys]
        perm = _order(mask, kv)
        sk = [k[perm] for k in kv]
        valid = mask[perm]
        diff = jnp.zeros(cap - 1, bool)
        for s in sk:
            diff = diff | (s[1:] != s[:-1])
        new = jnp.concatenate([jnp.ones(1, bool), diff]) & valid
        gid_sorted = _cumsum(new.astype(jnp.int32)) - 1
        gid = jnp.zeros(cap, jnp.int32).at[perm].set(
            jnp.where(valid, gid_sorted, cap))
        slot = jnp.where(new, gid_sorted, cap)
        out = {k: jnp.zeros(cap, jnp.int64).at[slot].set(s, mode="drop")
               .astype(cols[k].dtype) for k, s in zip(keys, sk)}
        out_mask = _iota(cap) < jnp.sum(new)
    else:
        gid = jnp.where(mask, 0, cap)
        out = {}
        out_mask = _iota(cap) < 1           # one group, even of no rows
    ones = jnp.ones(cap, jnp.float64)
    with jax.named_scope("segment"):
        for how, (exprs, outs) in _segments(aggs).items():
            # one scatter of [cap, A] rows, A distinct columns: its cost is
            # mostly per row; the values are built apart, not inside the
            # scatter's loop
            v = lax.optimization_barrier(jnp.stack(
                [ones if e is None else _column(cols, e, cap, np.float64)
                 for e in exprs], axis=1))
            acc = jnp.full((cap, len(exprs)), _INIT[how], jnp.float64).at[gid]
            seg = getattr(acc, how)(v, mode="drop")
            res = [seg[:, j] for j in range(len(exprs))]
            out.update((name, res[j]) for name, j in outs)
    return out, out_mask


@functools.partial(jax.jit, static_argnames=("spec",))
def _program(cols, n, builds, n_parts, spec):
    """The task pipeline. spec = (ops JSON, partition key or None,
    partition-id bound, per-join output capacities). Each operator's ops
    sit in a name scope: ``filter``, ``compute``, ``join``,
    ``aggregate`` (its segment scatters in ``aggregate/segment``),
    ``partition`` (the hash) and ``output`` (the final sort and
    gather)."""
    ops_json, part_key, p_cap, caps = spec
    cap = next(iter(cols.values())).shape[0]
    mask = _iota(cap) < n
    totals = []
    for op in json.loads(ops_json):
        kind = op["op"]
        if kind == "filter":
            with jax.named_scope("filter"):
                mask = mask & jnp.asarray(
                    _eval(cols, op["pred"], jnp)).astype(bool)
        elif kind == "project":
            cols = {c: cols[c] for c in op["columns"]}
        elif kind == "compute":
            with jax.named_scope("compute"):
                cols = {**cols, op["name"]: _column(cols, op["expr"],
                                                    mask.shape[0])}
        elif kind == "partial_agg":
            with jax.named_scope("aggregate"):
                cols, mask = _aggregate(cols, mask, op["keys"], op["aggs"])
        else:
            bcols, bn = builds[op["table"]]
            with jax.named_scope("join"):
                cols, mask, total = _join(cols, mask, bcols, bn,
                                          op["lkey"], op["rkey"],
                                          caps[len(totals)])
            totals.append(total)
    # output: valid rows first (partition-major when partitioned), stable
    if part_key is not None:
        with jax.named_scope("partition"):
            pid = (splitmix64(cols[part_key].astype(jnp.int64))
                   % n_parts).astype(jnp.int32)
    with jax.named_scope("output"):
        if part_key is None:
            order = _order(mask)
            bounds = None
        else:
            order = _order(mask, [pid])
            spid = jnp.where(mask[order], pid[order], p_cap)
            bounds = jnp.searchsorted(spid, _iota(p_cap + 1), side="left")
        out = {c: v[order] for c, v in cols.items()}
        return out, jnp.sum(mask), bounds, tuple(totals)


@functools.partial(jax.jit, static_argnames=("m",))
def _head(cols, m):
    return {c: v[:m] for c, v in cols.items()}


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def _spec(ops: list, t: Table, builds: dict, partition, cap: int):
    """(static program spec, output column names, output dictionaries)
    for a task whose input is padded to ``cap`` rows; each join starts
    with that capacity."""
    resolved, names, dicts = _schema(ops, t, builds)
    n_joins = sum(op["op"] in ("join", "broadcast_join") for op in ops)
    part_key, p_cap = (None, 0) if partition is None else \
        (partition[0], bucket(partition[1], 8))
    return ((json.dumps(resolved, sort_keys=True), part_key, p_cap,
             (cap,) * n_joins), names, dicts)


def run(t: Table, ops: list, builds: dict[str, Table],
        partition: tuple[str, int] | None = None):
    """Run a task's ops on the device: the chip of the enclosing
    ``on_chip`` block, where the probe side and every build side are put
    so that the program runs there, else JAX's default device.

    ``ops`` are plan ops (filter / project / compute / partial_agg /
    broadcast_join) plus ``{"op": "join", "table", "lkey", "rkey"}``;
    a join op names its build side in ``builds``. Returns the output
    Table, or with ``partition=(key, n)`` its n hash partitions, as
    ``relational.ops.op_partition`` cuts them.

    Each phase runs in its ``repro.ops.*`` span, and the call records the
    ``spans.ROWS`` and ``spans.ROWS_PADDED`` counters, inside ``on_chip``
    the padded rows again under the chip's ``spans.rows_padded_chip``,
    and with a partial aggregate ``spans.AGG_COLUMNS`` and
    ``spans.AGG_SCATTERS`` (``obs.spans``).
    """
    device = getattr(_placed, "device", None)
    n_parts = np.uint64(1 if partition is None else partition[1])
    joined = {op["table"] for op in ops
              if op["op"] in ("join", "broadcast_join")}
    with jax.enable_x64(True):
        with spans.span(spans.OPS_STAGE):
            spec, names, dicts = _spec(ops, t, builds, partition,
                                       bucket(len(t)))
            cols, n = _to_device(t, device)
            dev_builds = {b: _to_device(builds[b], device) for b in joined}
        padded = bucket(len(t)) + sum(bucket(len(builds[b])) for b in joined)
        runs = 0
        while True:
            with spans.span(spans.OPS_LAUNCH):
                out, n_dev, bounds, totals = _program(cols, n, dev_builds,
                                                      n_parts, spec=spec)
            runs += 1
            with spans.span(spans.OPS_WAIT):
                n_out, bounds, totals = jax.device_get(
                    (n_dev, bounds, totals))
            caps = spec[3]
            if all(tot <= c for tot, c in zip(totals, caps)):
                break
            spec = spec[:3] + (tuple(max(c, bucket(int(tot)))
                                     for tot, c in zip(totals, caps)),)
        jax.monitoring.record_event(
            TASK_EVENT, platform=next(iter(n_dev.devices())).platform)
        jax.monitoring.record_scalar(
            spans.ROWS, len(t) + sum(len(builds[b]) for b in joined))
        jax.monitoring.record_scalar(spans.ROWS_PADDED, runs * padded)
        if device is not None:
            jax.monitoring.record_scalar(spans.rows_padded_chip(device.id),
                                         runs * padded)
        segs = [_segments(op["aggs"]) for op in ops
                if op["op"] == "partial_agg"]
        if segs:
            jax.monitoring.record_scalar(spans.AGG_COLUMNS, sum(
                len(outs) for seg in segs for _, outs in seg.values()))
            jax.monitoring.record_scalar(
                spans.AGG_SCATTERS, sum(len(seg) for seg in segs))
        with spans.span(spans.OPS_FETCH):
            n_out = int(n_out)
            cap_out = next(iter(out.values())).shape[0] if out else 0
            m = min(bucket(n_out), cap_out)
            host = jax.device_get(_head(out, m) if m < cap_out else out)
    with spans.span(spans.OPS_SPLIT):
        cols_out = {}
        for name in names:
            a = host[name][:n_out]
            cols_out[name] = DictColumn(a, dicts[name]) if name in dicts \
                else a
        table = Table(cols_out)
        if partition is None:
            return table
        if not n_out:
            return [Table({})] * partition[1]
        return [table.take(slice(int(bounds[i]), int(bounds[i + 1])))
                for i in range(partition[1])]
