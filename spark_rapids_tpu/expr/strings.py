"""String expressions on device byte planes.

Reference parity: org/apache/spark/sql/rapids/stringFunctions.scala and the
string pieces of GpuCast.scala (CastStrings JNI).

Device representation is offsets(int32[cap+1]) + bytes(uint8). Kernels are
branch-free over byte planes; per-row variable length is handled with
searchsorted row mapping (same trick as kernels.gather) or bounded
while_loops over the batch max length. Ops we cannot (yet) express
efficiently on device report supported_on_tpu() = False and the planner
falls the enclosing exec back to CPU -- the reference's per-op fallback
discipline.
"""
from __future__ import annotations

import copy
from typing import List

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnVector, round_capacity
from spark_rapids_tpu.expr.core import (
    CpuCol, EvalCtx, Expression, SparkException, _flat_view, _valid_of,
)


def _lens(col: ColumnVector) -> jax.Array:
    if col.is_dict:
        o = col.data["dict_offsets"]
        return (o[1:] - o[:-1])[col.data["codes"]]
    o = col.data["offsets"]
    return o[1:] - o[:-1]


def _starts(col: ColumnVector) -> jax.Array:
    return col.data["offsets"][:-1]


def _flatten(c: ColumnVector, ctx) -> ColumnVector:
    if not c.is_dict:
        return c
    from spark_rapids_tpu.ops.kernels import flatten_dict_column
    return flatten_dict_column(c, ctx.num_rows)


def _lift_unary(ctx, c: ColumnVector, compute) -> ColumnVector:
    """Evaluate a unary string op. compute(flat_col, row_cap) returns a
    ColumnVector over the flat row space (validity ignored). Dict-encoded
    children evaluate over the VOCAB — O(vocab) instead of O(rows) — and
    map back by code; string-valued results stay dict-encoded with a new
    vocab (zero per-row byte work)."""
    valid = _valid_of(c, ctx)
    if c.is_dict:
        flat = _flat_view(c)
        res = compute(flat, flat.capacity)
        codes = c.data["codes"]
        if res.is_string:
            # transformed vocab may contain duplicates (upper('a')==
            # upper('A')) — mark codes non-unique so bucket-by-code
            # grouping falls back to content-hash grouping
            return ColumnVector(T.STRING, {
                "codes": codes,
                "dict_offsets": res.data["offsets"],
                "dict_bytes": res.data["bytes"]}, c.validity,
                dict_unique=False)
        return ColumnVector(res.dtype, res.data[codes], valid)
    res = compute(c, c.capacity)
    return ColumnVector(res.dtype, res.data, valid)


class StringLength(Expression):
    """length(): number of UTF-8 characters (not bytes), like Spark."""

    def __init__(self, child):
        self.children = [child]

    def data_type(self):
        return T.INT32

    def with_children(self, children):
        return StringLength(children[0])

    def eval_tpu(self, ctx):
        c = self.children[0].eval_tpu(ctx)

        def compute(flat, cap):
            raw = flat.data["bytes"]
            o = flat.data["offsets"]
            # count non-continuation bytes per row: prefix-sum over bytes
            is_start = (raw & 0xC0) != 0x80
            csum = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                    jnp.cumsum(is_start.astype(jnp.int32))])
            nchars = csum[o[1:]] - csum[o[:-1]]
            return ColumnVector(T.INT32, nchars.astype(jnp.int32), None)

        return _lift_unary(ctx, c, compute)

    def eval_cpu(self, cols, ansi=False):
        c = self.children[0].eval_cpu(cols, ansi)
        vals = np.array([len(s) if isinstance(s, str) else 0 for s in c.values], np.int32)
        return CpuCol(T.INT32, vals, c.valid)


class _CaseMap(Expression):
    """ASCII upper/lower; rows containing non-ASCII map byte-wise only for
    ASCII letters (Spark does full Unicode -- non-ASCII batches should be
    tagged off-device by the planner via contains_non_ascii stats; round 1
    applies ASCII mapping and documents the incompat)."""

    upper: bool = True

    def __init__(self, child):
        self.children = [child]

    def data_type(self):
        return T.STRING

    def with_children(self, children):
        return type(self)(children[0])

    def eval_tpu(self, ctx):
        c = self.children[0].eval_tpu(ctx)

        def compute(flat, cap):
            raw = flat.data["bytes"]
            from spark_rapids_tpu.ops import pallas_kernels as PK
            if PK.enabled() and raw.shape[0] % 4096 == 0:
                shifted = PK.ascii_case_map_pallas(raw, self.upper)
            elif self.upper:
                shifted = jnp.where((raw >= 97) & (raw <= 122), raw - 32, raw)
            else:
                shifted = jnp.where((raw >= 65) & (raw <= 90), raw + 32, raw)
            return ColumnVector(T.STRING, {"offsets": flat.data["offsets"],
                                           "bytes": shifted}, None)

        return _lift_unary(ctx, c, compute)

    def eval_cpu(self, cols, ansi=False):
        c = self.children[0].eval_cpu(cols, ansi)
        f = str.upper if self.upper else str.lower
        vals = np.array([f(s) if isinstance(s, str) else s for s in c.values], object)
        return CpuCol(T.STRING, vals, c.valid)


class Upper(_CaseMap):
    upper = True


class Lower(_CaseMap):
    upper = False


class Substring(Expression):
    """substring(str, pos, len): 1-based pos, negative counts from end;
    character (not byte) positions, like Spark."""

    def __init__(self, child, pos: int, length: int = 1 << 30):
        self.children = [child]
        self.pos = pos
        self.length = length

    def data_type(self):
        return T.STRING

    def _params(self):
        return f"{self.pos},{self.length}"

    def with_children(self, children):
        return Substring(children[0], self.pos, self.length)

    def eval_tpu(self, ctx):
        c = self.children[0].eval_tpu(ctx)
        return _lift_unary(ctx, c, self._compute)

    def _compute(self, flat, cap):
        o = flat.data["offsets"]
        raw = flat.data["bytes"]
        is_start = ((raw & 0xC0) != 0x80).astype(jnp.int32)
        char_csum = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(is_start)])
        nchars = char_csum[o[1:]] - char_csum[o[:-1]]
        # resolve 1-based/negative start to 0-based char index
        if self.pos > 0:
            start_char = jnp.minimum(self.pos - 1, nchars)
        elif self.pos == 0:
            start_char = jnp.zeros_like(nchars)
        else:
            start_char = jnp.maximum(nchars + self.pos, 0)
        take = max(self.length, 0)
        end_char = jnp.minimum(start_char + take, nchars)
        # char index -> byte offset: byte b is the k-th char start where
        # k = char_csum[b] - char_csum[row_start]. Build per-row byte offsets
        # by searching the cumulative char counts.
        # byte position of char t in a row = last byte index whose prefix
        # char-count equals csum[row_start]+t (side='right'-1 lands past any
        # UTF-8 continuation bytes onto the next char-start byte).
        target_start = char_csum[o[:-1]] + start_char
        target_end = char_csum[o[:-1]] + end_char
        byte_start = jnp.searchsorted(char_csum, target_start, side="right").astype(jnp.int32) - 1
        byte_end = jnp.searchsorted(char_csum, target_end, side="right").astype(jnp.int32) - 1
        byte_start = jnp.clip(byte_start, o[:-1], o[1:])
        byte_end = jnp.clip(byte_end, byte_start, o[1:])
        out_lens = byte_end - byte_start
        new_off = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                   jnp.cumsum(out_lens).astype(jnp.int32)])
        nb = raw.shape[0]
        b = jnp.arange(nb, dtype=jnp.int32)
        row = jnp.clip(jnp.searchsorted(new_off, b, side="right").astype(jnp.int32) - 1,
                       0, nchars.shape[0] - 1)
        src = jnp.clip(byte_start[row] + (b - new_off[row]), 0, nb - 1)
        out_bytes = jnp.where(b < new_off[-1], raw[src], 0).astype(jnp.uint8)
        return ColumnVector(T.STRING, {"offsets": new_off, "bytes": out_bytes}, None)

    def eval_cpu(self, cols, ansi=False):
        c = self.children[0].eval_cpu(cols, ansi)
        out = []
        for s in c.values:
            if not isinstance(s, str):
                out.append(s)
                continue
            if self.pos > 0:
                start = self.pos - 1
            elif self.pos == 0:
                start = 0
            else:
                start = max(len(s) + self.pos, 0)
            out.append(s[start: start + max(self.length, 0)])
        return CpuCol(T.STRING, np.array(out, object), c.valid)


class ConcatStrings(Expression):
    """concat(s1, s2, ...): null if any input null (Spark concat)."""

    def __init__(self, *children):
        self.children = list(children)

    def data_type(self):
        return T.STRING

    def with_children(self, children):
        return ConcatStrings(*children)

    def eval_tpu(self, ctx):
        parts = [_flatten(c.eval_tpu(ctx), ctx) for c in self.children]
        valid = _valid_of(parts[0], ctx)
        for p in parts[1:]:
            valid = valid & _valid_of(p, ctx)
        lens = sum(_lens(p) for p in parts)
        lens = jnp.where(valid, lens, 0)
        new_off = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                   jnp.cumsum(lens).astype(jnp.int32)])
        total_cap = round_capacity(int(sum(int(p.data["bytes"].shape[0]) for p in parts)))
        b = jnp.arange(total_cap, dtype=jnp.int32)
        row = jnp.clip(jnp.searchsorted(new_off, b, side="right").astype(jnp.int32) - 1,
                       0, ctx.capacity - 1)
        pos = b - new_off[row]  # position within the concatenated row
        out = jnp.zeros(total_cap, jnp.uint8)
        acc = jnp.zeros(ctx.capacity, jnp.int32)  # running char offset per row
        for p in parts:
            pl = _lens(p)
            in_part = (pos >= acc[row]) & (pos < acc[row] + pl[row])
            src = jnp.clip(_starts(p)[row] + (pos - acc[row]), 0,
                           p.data["bytes"].shape[0] - 1)
            out = jnp.where(in_part, p.data["bytes"][src], out)
            acc = acc + pl
        out = jnp.where(b < new_off[-1], out, 0).astype(jnp.uint8)
        return ColumnVector(T.STRING, {"offsets": new_off, "bytes": out}, valid)

    def eval_cpu(self, cols, ansi=False):
        parts = [c.eval_cpu(cols, ansi) for c in self.children]
        valid = parts[0].valid.copy()
        for p in parts[1:]:
            valid = valid & p.valid
        out = []
        for i in range(len(valid)):
            if valid[i]:
                out.append("".join(str(p.values[i]) for p in parts))
            else:
                out.append(None)
        return CpuCol(T.STRING, np.array(out, object), valid)


class _LiteralMatch(Expression):
    """A string against literal runs: it starts with `prefix`, ends with
    `suffix`, and holds `middles` in order between them, nothing
    overlapping (`LIKE 'prefix%m1%m2%suffix'`). One kernel over the byte
    plane (ops/strmatch.match_runs) serves startswith, endswith, contains
    and every LIKE made of literal runs; a dictionary column answers on its
    vocabulary through the same kernel (_lift_unary)."""

    #: the match runs over the bytes, not the rows: a fused stage that
    #: holds one and meets a flat column runs its operators apart
    #: (tpu_nodes.meets_flat_string), and the Filter is timed as the match
    plane_match = True

    def __init__(self, child, prefix: str = "", middles=(), suffix: str = ""):
        self.children = [child]
        self.prefix, self.suffix = prefix, suffix
        self.middles = tuple(middles)

    def data_type(self):
        return T.BOOLEAN

    def _params(self):
        return repr((self.prefix, self.middles, self.suffix))

    def with_children(self, children):
        new = copy.copy(self)   # keeps the subclass (its name is the
        new.children = [children[0]]  # expression's fingerprint)
        return new

    def eval_tpu(self, ctx):
        c = self.children[0].eval_tpu(ctx)
        return _lift_unary(ctx, c, self._compute)

    def _compute(self, flat, cap):
        from spark_rapids_tpu.ops.strmatch import match_runs
        res = match_runs(flat.data["offsets"], flat.data["bytes"],
                         flat.str_width, self.prefix.encode("utf-8"),
                         [m.encode("utf-8") for m in self.middles],
                         self.suffix.encode("utf-8"))
        return ColumnVector(T.BOOLEAN, res, None)

    def matches(self, s: str) -> bool:
        """The plain-Python twin (the CPU path and the tests' oracle)."""
        if len(s) < len(self.prefix) + len(self.suffix) or \
                not s.startswith(self.prefix) or not s.endswith(self.suffix):
            return False
        pos, stop = len(self.prefix), len(s) - len(self.suffix)
        for m in self.middles:
            i = s.find(m, pos, stop)
            if i < 0:
                return False
            pos = i + len(m)
        return True

    def eval_cpu(self, cols, ansi=False):
        c = self.children[0].eval_cpu(cols, ansi)
        vals = np.array([self.matches(s) if isinstance(s, str) else False
                         for s in c.values], np.bool_)
        return CpuCol(T.BOOLEAN, vals, c.valid)


def plane_matches(e) -> list:
    """The string matches in `e` that run over a column's byte plane."""
    own = [e] if getattr(e, "plane_match", False) else []
    return own + [m for c in e.children for m in plane_matches(c)]


class StartsWith(_LiteralMatch):
    def __init__(self, child, pattern: str):
        super().__init__(child, prefix=pattern)


class EndsWith(_LiteralMatch):
    def __init__(self, child, pattern: str):
        super().__init__(child, suffix=pattern)


class Contains(_LiteralMatch):
    def __init__(self, child, pattern: str):
        super().__init__(child, middles=(pattern,))


class Like(Expression):
    """SQL LIKE. A pattern of literal runs between `%` is one pass kernel
    over the byte plane (_LiteralMatch), one without `%` an equality; a
    `_` goes to the device NFA (the reference's regex-transpile-or-reject
    strategy, RegexParser.scala), and what that cannot take runs on the
    CPU and marks the expression unsupported on device."""

    def __init__(self, child, pattern: str, escape: str = "\\"):
        self.children = [child]
        self.pattern = pattern
        self.escape = escape

    @property
    def plane_match(self) -> bool:
        """Runs over the byte plane (_LiteralMatch.plane_match): not a
        pattern without `%` (an equality) nor one of `%` alone."""
        return self.pattern.replace("%", "") != "" and not isinstance(
            self._transpile(), _StringEquals)

    def data_type(self):
        return T.BOOLEAN

    def _params(self):
        return repr(self.pattern)

    def with_children(self, children):
        return Like(children[0], self.pattern, self.escape)

    def _transpile(self):
        """Return an equivalent device expression, or None."""
        p = self.pattern
        esc = self.escape
        # tokenize
        literal = []
        tokens: List[str] = []
        i = 0
        while i < len(p):
            ch = p[i]
            if ch == esc and i + 1 < len(p):
                literal.append(p[i + 1])
                tokens.append("LIT")
                i += 2
            elif ch == "%":
                tokens.append("%")
                literal.append("")
                i += 1
            elif ch == "_":
                tokens.append("_")
                literal.append("")
                i += 1
            else:
                tokens.append("LIT")
                literal.append(ch)
                i += 1
        if "_" in tokens:
            return None
        # split literal runs by %
        runs: List[str] = []
        cur = ""
        for tk, li in zip(tokens, literal):
            if tk == "%":
                runs.append(cur)
                cur = ""
            else:
                cur += li
        runs.append(cur)
        child = self.children[0]
        if len(runs) == 1:
            return _StringEquals(child, runs[0])
        if not any(runs):
            return None  # only %: trivially true; handled below
        return _LiteralMatch(child, runs[0], runs[1:-1], runs[-1])

    def _nfa(self):
        from spark_rapids_tpu.expr import regex as RX
        if not hasattr(self, "_nfa_cache"):
            try:
                # LIKE wildcards match newlines too (CPU path uses
                # re.DOTALL): translate via (.|\n), not bare `.`
                out = []
                i = 0
                p, esc = self.pattern, self.escape
                while i < len(p):
                    ch = p[i]
                    if ch == esc and i + 1 < len(p):
                        ch = p[i + 1]
                        i += 2
                    elif ch == "%":
                        out.append("(.|\n)*")
                        i += 1
                        continue
                    elif ch == "_":
                        out.append("(.|\n)")
                        i += 1
                        continue
                    else:
                        i += 1
                    out.append("\\" + ch if ch in ".^$*+?()[]{}|\\/-" else ch)
                self._nfa_cache = RX.compile_pattern("".join(out), mode="match")
            except RX.RegexUnsupported:
                self._nfa_cache = None
        return self._nfa_cache

    def supported_on_tpu(self):
        return (self._transpile() is not None
                or self.pattern.replace("%", "") == ""
                or self._nfa() is not None)

    def eval_tpu(self, ctx):
        t = self._transpile()
        if t is not None:
            return t.eval_tpu(ctx)
        if self.pattern.replace("%", "") == "":
            c = self.children[0].eval_tpu(ctx)
            return ColumnVector(T.BOOLEAN, jnp.ones(ctx.capacity, jnp.bool_),
                                _valid_of(c, ctx))
        # general LIKE (e.g. '_' wildcards): full-match device NFA
        from spark_rapids_tpu.expr import regex as RX
        nfa = self._nfa()
        if nfa is None:
            raise NotImplementedError(f"LIKE pattern {self.pattern!r} on device")
        c = self.children[0].eval_tpu(ctx)

        def compute(flat, cap):
            if isinstance(flat.data["bytes"], jax.core.Tracer):
                from spark_rapids_tpu.runtime import compile_cache as _cc
                _cc.note_traced("like_nfa_traced")
            res = RX.nfa_eval(nfa, flat.data["offsets"], flat.data["bytes"], None)
            return ColumnVector(T.BOOLEAN, res, None)

        return _lift_unary(ctx, c, compute)

    def eval_cpu(self, cols, ansi=False):
        import re
        c = self.children[0].eval_cpu(cols, ansi)
        rx = _like_to_regex(self.pattern, self.escape)
        prog = re.compile(rx, re.DOTALL)
        vals = np.array([bool(prog.fullmatch(s)) if isinstance(s, str) else False
                         for s in c.values], np.bool_)
        return CpuCol(T.BOOLEAN, vals, c.valid)


def _like_to_regex(pattern: str, esc: str) -> str:
    import re
    out = []
    i = 0
    while i < len(pattern):
        ch = pattern[i]
        if ch == esc and i + 1 < len(pattern):
            out.append(re.escape(pattern[i + 1]))
            i += 2
        elif ch == "%":
            out.append(".*")
            i += 1
        elif ch == "_":
            out.append(".")
            i += 1
        else:
            out.append(re.escape(ch))
            i += 1
    return "".join(out)


class RLike(Expression):
    """Spark RLIKE: Java regex, match-anywhere. Patterns inside the device
    subset run as a bit-parallel NFA over byte planes (expr/regex.py);
    others fall back to CPU `re` — the reference's RegexParser
    transpile-or-reject contract."""

    plane_match = True

    def __init__(self, child, pattern: str):
        self.children = [child]
        self.pattern = pattern
        self._nfa = None
        self._nfa_err = None

    def data_type(self):
        return T.BOOLEAN

    def _params(self):
        return repr(self.pattern)

    def with_children(self, children):
        return RLike(children[0], self.pattern)

    def _compiled(self):
        from spark_rapids_tpu.expr import regex as RX
        if self._nfa is None and self._nfa_err is None:
            try:
                self._nfa = RX.compile_pattern(self.pattern, mode="find")
            except RX.RegexUnsupported as e:
                self._nfa_err = str(e)
        return self._nfa

    def supported_on_tpu(self):
        return self._compiled() is not None

    def eval_tpu(self, ctx):
        from spark_rapids_tpu.expr import regex as RX
        nfa = self._compiled()
        if nfa is None:
            raise NotImplementedError(
                f"regex {self.pattern!r} on device: {self._nfa_err}")
        c = self.children[0].eval_tpu(ctx)

        def compute(flat, cap):
            res = RX.nfa_eval(nfa, flat.data["offsets"], flat.data["bytes"],
                              None)
            return ColumnVector(T.BOOLEAN, res, None)

        return _lift_unary(ctx, c, compute)

    def eval_cpu(self, cols, ansi=False):
        import re
        c = self.children[0].eval_cpu(cols, ansi)
        prog = re.compile(self.pattern)
        vals = np.array([bool(prog.search(s)) if isinstance(s, str) else False
                         for s in c.values], np.bool_)
        return CpuCol(T.BOOLEAN, vals, c.valid)


class _RegexCpuBase(Expression):
    """regexp_extract / regexp_replace: capture-group semantics need a
    backtracking engine — CPU-only (tagged unsupported on device so the
    enclosing exec falls back, reference behavior for unsupported regex)."""

    def data_type(self):
        return T.STRING

    def supported_on_tpu(self):
        return False

    def eval_tpu(self, ctx):
        raise NotImplementedError("capture-group regex runs on CPU")


class RegexpExtract(_RegexCpuBase):
    """regexp_extract: capture-group extraction. Alternation-free
    patterns within the tagged-NFA subset run ON DEVICE (expr/regex.py
    compile_extract — the reference transpiles to the cudf regex engine
    the same transpile-or-reject way, RegexParser.scala); everything
    else falls back to the CPU tier."""

    def __init__(self, child, pattern: str, group: int = 1):
        self.children = [child]
        self.pattern = pattern
        self.group = group
        from spark_rapids_tpu.expr.regex import (
            RegexUnsupported, compile_extract)
        try:
            self._tagged = compile_extract(pattern, group)
            self._nfa_err = None
        except RegexUnsupported as e:
            self._tagged = None
            self._nfa_err = str(e)

    def _params(self):
        return f"{self.pattern!r},{self.group}"

    def with_children(self, children):
        return RegexpExtract(children[0], self.pattern, self.group)

    def supported_on_tpu(self):
        return self._tagged is not None

    def eval_tpu(self, ctx):
        from spark_rapids_tpu.expr.regex import nfa_extract
        c = self.children[0].eval_tpu(ctx)
        t = self._tagged

        def compute(flat, cap):
            off = flat.data["offsets"][: cap + 1].astype(jnp.int32)
            raw = flat.data["bytes"]
            has, g0, g1 = nfa_extract(t, off, raw)
            lens = jnp.where(has, g1 - g0, 0)
            new_off = jnp.concatenate(
                [jnp.zeros(1, jnp.int32),
                 jnp.cumsum(lens).astype(jnp.int32)])
            bcap = int(raw.shape[0])
            b = jnp.arange(bcap, dtype=jnp.int32)
            row = jnp.clip(
                jnp.searchsorted(new_off, b, side="right").astype(jnp.int32)
                - 1, 0, cap - 1)
            src = jnp.clip(off[row] + g0[row] + (b - new_off[row]),
                           0, bcap - 1)
            out_bytes = jnp.where(b < new_off[-1], raw[src],
                                  0).astype(jnp.uint8)
            return ColumnVector(T.STRING, {"offsets": new_off,
                                           "bytes": out_bytes}, None)

        return _lift_unary(ctx, c, compute)

    def eval_cpu(self, cols, ansi=False):
        import re
        c = self.children[0].eval_cpu(cols, ansi)
        prog = re.compile(self.pattern)
        if self.group > prog.groups or self.group < 0:
            raise ValueError(
                f"regexp_extract group {self.group} out of range for "
                f"{self.pattern!r} ({prog.groups} groups)")
        out = []
        for s in c.values:
            if not isinstance(s, str):
                out.append(None)
                continue
            m = prog.search(s)
            # Spark: "" for no match AND for a non-participating group
            out.append((m.group(self.group) or "") if m else "")
        return CpuCol(T.STRING, np.array(out, object), c.valid)


class RegexpReplace(_RegexCpuBase):
    """regexp_replace: replace-all. Patterns in the tagged-NFA subset
    with a LITERAL replacement (<= 8 bytes, no $n backrefs) run ON
    DEVICE: one match-span scan (expr/regex.py nfa_match_spans) plus a
    byte-plane splice — the transpile-or-reject discipline of the
    reference's RegexParser.scala. Backrefs and everything outside the
    subset fall back to the CPU tier."""

    _MAX_DEVICE_REPL = 8

    def __init__(self, child, pattern: str, replacement: str):
        self.children = [child]
        self.pattern = pattern
        self.replacement = replacement
        self._tagged = None
        self._nfa_err = None
        import re as _re
        if _re.search(r"\$\d", replacement):
            self._nfa_err = "backref in replacement"
        elif len(replacement.encode()) > self._MAX_DEVICE_REPL:
            self._nfa_err = "replacement too long for device splice"
        else:
            from spark_rapids_tpu.expr.regex import (
                RegexUnsupported, compile_replace)
            try:
                self._tagged = compile_replace(pattern)
            except RegexUnsupported as e:
                self._nfa_err = str(e)

    def _params(self):
        return f"{self.pattern!r},{self.replacement!r}"

    def with_children(self, children):
        return RegexpReplace(children[0], self.pattern, self.replacement)

    def supported_on_tpu(self):
        return self._tagged is not None

    def eval_tpu(self, ctx):
        from spark_rapids_tpu.expr.regex import nfa_match_spans
        if self._tagged is None:
            raise NotImplementedError(
                f"regexp_replace {self.pattern!r} on device: "
                f"{self._nfa_err}")
        c = self.children[0].eval_tpu(ctx)
        t = self._tagged
        rep = np.frombuffer(self.replacement.encode(), np.uint8)
        R = int(rep.shape[0])

        def compute(flat, cap):
            off = flat.data["offsets"][: cap + 1].astype(jnp.int32)
            raw = flat.data["bytes"]
            nbytes = int(raw.shape[0])
            flags, slen = nfa_match_spans(t, off, raw)
            fi = flags.astype(jnp.int32)
            # in-match mask via the range-delta trick (spans never
            # cross row boundaries)
            delta = jnp.zeros(nbytes + 1, jnp.int32)
            b_idx = jnp.arange(nbytes, dtype=jnp.int32)
            delta = delta.at[jnp.where(flags, b_idx, nbytes)].add(fi)
            delta = delta.at[jnp.where(flags, b_idx + slen, nbytes)].add(
                -fi)
            inm = jnp.cumsum(delta[:nbytes]) > 0
            keep = ~inm & (b_idx < off[cap])
            # output layout: per byte, kept-bytes-so-far and
            # matches-so-far (exclusive prefix sums)
            kept_x = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                      jnp.cumsum(keep.astype(jnp.int32))])
            m_x = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                   jnp.cumsum(fi)])
            new_off = kept_x[off] + R * m_x[off]
            new_off = new_off - new_off[0]
            out_cap = max(int(nbytes) * max(1, R), 8)
            row = jnp.clip(jnp.searchsorted(
                off, b_idx, side="right").astype(jnp.int32) - 1,
                0, cap - 1)
            out_base = new_off[row] + (kept_x[b_idx] - kept_x[off[row]]) \
                + R * (m_x[b_idx] - m_x[off[row]])
            out = jnp.zeros(out_cap, jnp.uint8)
            out = out.at[jnp.where(keep, out_base, out_cap)].set(
                raw, mode="drop")
            for j in range(R):
                out = out.at[jnp.where(flags, out_base + j, out_cap)].set(
                    jnp.uint8(rep[j]), mode="drop")
            return ColumnVector(T.STRING,
                                {"offsets": new_off.astype(jnp.int32),
                                 "bytes": out}, None)

        return _lift_unary(ctx, c, compute)

    def eval_cpu(self, cols, ansi=False):
        import re
        c = self.children[0].eval_cpu(cols, ansi)
        prog = re.compile(self.pattern)
        # Java $1 -> python \1 backrefs
        repl = re.sub(r"\$(\d)", r"\\\1", self.replacement)
        vals = np.array([prog.sub(repl, s) if isinstance(s, str) else s
                         for s in c.values], object)
        return CpuCol(T.STRING, vals, c.valid)


class _StringEquals(Expression):
    def __init__(self, child, value: str):
        self.children = [child]
        self.value = value

    def data_type(self):
        return T.BOOLEAN

    def with_children(self, children):
        return _StringEquals(children[0], self.value)

    def eval_tpu(self, ctx):
        from spark_rapids_tpu.expr.core import EqualTo, Literal
        return EqualTo(self.children[0], Literal(self.value, T.STRING)).eval_tpu(ctx)

    def eval_cpu(self, cols, ansi=False):
        c = self.children[0].eval_cpu(cols, ansi)
        vals = np.array([s == self.value if isinstance(s, str) else False
                         for s in c.values], np.bool_)
        return CpuCol(T.BOOLEAN, vals, c.valid)


# ---------------------------------------------------------------------------
# Casts involving strings (reference GpuCast string paths / CastStrings JNI)
# ---------------------------------------------------------------------------

_DIGITS = np.frombuffer(b"0123456789", np.uint8)


def _render_int64_tpu(values: jax.Array, valid: jax.Array) -> ColumnVector:
    """int64 -> decimal string rendering on device: compute per-row digit
    count, then scatter digits (branch-free, fixed 20-byte max per row)."""
    cap = values.shape[0]
    neg = values < 0
    # abs in uint64 to handle INT64_MIN
    mag = jnp.where(neg, (~values.astype(jnp.uint64)) + jnp.uint64(1),
                    values.astype(jnp.uint64))
    # digit count via comparisons (max 20 digits for uint64)
    ndig = jnp.ones(cap, jnp.int32)
    p = jnp.uint64(10)
    for k in range(1, 20):
        ndig = ndig + (mag >= p).astype(jnp.int32)
        p = p * jnp.uint64(10)
    lens = ndig + neg.astype(jnp.int32)
    lens = jnp.where(valid, lens, 0)
    new_off = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(lens).astype(jnp.int32)])
    total = new_off[-1]
    bcap = cap * 20  # static upper bound
    b = jnp.arange(bcap, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(new_off, b, side="right").astype(jnp.int32) - 1,
                   0, cap - 1)
    pos = b - new_off[row]  # position within the rendered number
    is_sign = neg[row] & (pos == 0)
    # digit index from the right: ndig-1-(pos - has_sign)
    di = ndig[row] - 1 - (pos - neg[row].astype(jnp.int32))
    di = jnp.clip(di, 0, 19)
    # extract digit di (from least significant) of mag[row]
    mrow = mag[row]
    div = jnp.power(jnp.full(bcap, 10, jnp.uint64), di.astype(jnp.uint64))
    digit = ((mrow // div) % jnp.uint64(10)).astype(jnp.int32)
    ch = jnp.where(is_sign, np.uint8(45), (digit + 48).astype(jnp.uint8))
    out = jnp.where(b < total, ch, 0).astype(jnp.uint8)
    return ColumnVector(T.STRING, {"offsets": new_off, "bytes": out}, valid)


def _parse_int64_tpu(col: ColumnVector, valid: jax.Array, ctx: EvalCtx):
    """string -> int64: optional sign + digits, leading/trailing spaces
    trimmed, anything else -> null (non-ANSI Spark)."""
    o = col.data["offsets"]
    raw = col.data["bytes"]
    starts = o[:-1]
    ends = o[1:]
    nb = raw.shape[0]

    def at(pos):
        return raw[jnp.clip(pos, 0, nb - 1)]

    # trim spaces
    def trim(state):
        s, e = state
        lead = (s < e) & (at(s) == 32)
        tail = (e > s) & (at(e - 1) == 32)
        return jnp.where(lead, s + 1, s), jnp.where(tail, e - 1, e)

    def trim_cond(state):
        s, e = state
        lead = (s < e) & (at(s) == 32)
        tail = (e > s) & (at(e - 1) == 32)
        return jnp.any(lead | tail)

    s, e = lax.while_loop(trim_cond, trim, (starts, ends))
    first = at(s)
    has_sign = (first == 45) | (first == 43)
    neg = first == 45
    ds = s + has_sign.astype(jnp.int32)
    ok = (e > ds)

    def body(state):
        i, acc, good, done = state
        pos = ds + i
        active = (pos < e) & ~done
        byte = at(pos)
        is_digit = (byte >= 48) & (byte <= 57)
        acc2 = acc * 10 + (byte - 48).astype(jnp.int64)
        acc = jnp.where(active & is_digit, acc2, acc)
        good = good & (~active | is_digit)
        done = done | (pos >= e)
        return i + 1, acc, good, done

    def cond(state):
        i, _, _, done = state
        return ~jnp.all(done)

    n = starts.shape[0]
    init = (jnp.int32(0), jnp.zeros(n, jnp.int64), ok,
            jnp.zeros(n, jnp.bool_))
    _, acc, good, _ = lax.while_loop(cond, body, init)
    value = jnp.where(neg, -acc, acc)
    out_valid = valid & good
    if ctx is not None and ctx.ansi:
        ctx.add_error("CAST_INVALID_INPUT", valid & ~good)
    return value, out_valid


def cast_string_tpu(c: ColumnVector, dst: T.DataType, ctx: EvalCtx) -> ColumnVector:
    valid = _valid_of(c, ctx)
    if isinstance(dst, T.StringType):
        src = c.dtype
        if isinstance(src, T.BooleanType):
            from spark_rapids_tpu.expr.core import If, Literal, _RawCol
            return If(_RawCol(ColumnVector(T.BOOLEAN, c.data, valid)),
                      Literal("true", T.STRING),
                      Literal("false", T.STRING)).eval_tpu(ctx)
        if isinstance(src, T.DateType):
            from spark_rapids_tpu.expr import cast_kernels as CK
            return CK.render_date(c.data, valid)
        if isinstance(src, T.TimestampType):
            from spark_rapids_tpu.expr import cast_kernels as CK
            return CK.render_timestamp(c.data, valid)
        if src.is_integral:
            return _render_int64_tpu(c.data.astype(jnp.int64), valid)
        raise NotImplementedError(f"cast {src!r} -> string on device")
    if isinstance(c.dtype, T.StringType):
        if dst.is_integral:
            if c.is_dict:
                # parse the vocab once, gather values/validity by code
                flat = _flat_view(c)
                k = flat.capacity
                vv, vok = _parse_int64_tpu(flat, jnp.ones(k, jnp.bool_),
                                           ctx if not ctx.ansi else None)
                codes = c.data["codes"]
                out_valid = valid & vok[codes]
                if ctx.ansi:
                    ctx.add_error("CAST_INVALID_INPUT", valid & ~vok[codes])
                return ColumnVector(dst, vv[codes].astype(dst.np_dtype), out_valid)
            v64, out_valid = _parse_int64_tpu(c, valid, ctx)
            return ColumnVector(dst, v64.astype(dst.np_dtype), out_valid)
        if isinstance(dst, (T.Float32Type, T.Float64Type, T.DateType,
                            T.TimestampType)):
            from spark_rapids_tpu.expr import cast_kernels as CK
            if isinstance(dst, (T.Float32Type, T.Float64Type)):
                parse = CK.parse_f64
            elif isinstance(dst, T.DateType):
                parse = CK.parse_date
            else:
                parse = CK.parse_timestamp
            if c.is_dict:
                flat = _flat_view(c)
                vv, vok = parse(flat)
                codes = c.data["codes"]
                okc = vok[codes]
                out_valid = valid & okc
                if ctx.ansi:
                    ctx.add_error("CAST_INVALID_INPUT", valid & ~okc)
                vals = vv[codes]
            else:
                vals, vok = parse(c)
                out_valid = valid & vok
                if ctx.ansi:
                    ctx.add_error("CAST_INVALID_INPUT", valid & ~vok)
            return ColumnVector(dst, vals.astype(dst.np_dtype), out_valid)
        raise NotImplementedError(f"cast string -> {dst!r} on device")
    raise NotImplementedError


def cast_string_cpu(c: CpuCol, dst: T.DataType, ansi: bool) -> CpuCol:
    if isinstance(dst, T.StringType):
        src = c.dtype
        out = []
        for i, v in enumerate(c.values):
            if not c.valid[i]:
                out.append(None)
            elif isinstance(src, T.BooleanType):
                out.append("true" if v else "false")
            elif isinstance(src, (T.Float32Type, T.Float64Type)):
                out.append(_spark_float_str(float(v)))
            elif isinstance(src, T.DateType):
                import datetime
                out.append(str(datetime.date(1970, 1, 1)
                               + datetime.timedelta(days=int(v))))
            elif isinstance(src, T.TimestampType):
                import datetime
                dt = (datetime.datetime(1970, 1, 1)
                      + datetime.timedelta(microseconds=int(v)))
                s_iso = dt.isoformat(sep=" ")
                if "." in s_iso:  # Spark trims trailing fraction zeros
                    s_iso = s_iso.rstrip("0").rstrip(".")
                out.append(s_iso)
            elif isinstance(src, T.DecimalType):
                import decimal
                out.append(str(decimal.Decimal(int(v)).scaleb(-src.scale)))
            else:
                out.append(str(int(v)))
        return CpuCol(T.STRING, np.array(out, object),
                      c.valid.copy())
    # string -> X
    n = len(c.values)
    valid = c.valid.copy()
    if dst.is_integral:
        vals = np.zeros(n, np.int64)
        for i, s in enumerate(c.values):
            if not valid[i]:
                continue
            t = s.strip() if isinstance(s, str) else ""
            try:
                vals[i] = int(t)
            except ValueError:
                if ansi:
                    raise SparkException(f"[CAST_INVALID_INPUT] '{s}' to int")
                valid[i] = False
        return CpuCol(dst, vals.astype(dst.np_dtype), valid)
    if isinstance(dst, (T.Float32Type, T.Float64Type)):
        import re
        # Spark castToDouble = UTF8String.trim + Java Double.parseDouble:
        # case-SENSITIVE Infinity/NaN, no underscores, no bare 'inf'
        # (python float() is more lenient — do NOT use it directly)
        num_re = re.compile(
            r"[+-]?((\d+(\.\d*)?|\.\d+)([eE][+-]?\d+)?|Infinity|NaN)")
        vals = np.zeros(n, np.float64)
        for i, s in enumerate(c.values):
            if not valid[i]:
                continue
            t = _java_trim(s) if isinstance(s, str) else ""
            if num_re.fullmatch(t):
                vals[i] = float(t.replace("Infinity", "inf"))
            else:
                if ansi:
                    raise SparkException(f"[CAST_INVALID_INPUT] '{s}' to float")
                valid[i] = False
        return CpuCol(dst, vals.astype(dst.np_dtype), valid)
    if isinstance(dst, T.BooleanType):
        vals = np.zeros(n, np.bool_)
        for i, s in enumerate(c.values):
            if not valid[i]:
                continue
            t = (s.strip().lower() if isinstance(s, str) else "")
            if t in ("true", "t", "yes", "y", "1"):
                vals[i] = True
            elif t in ("false", "f", "no", "n", "0"):
                vals[i] = False
            else:
                if ansi:
                    raise SparkException(f"[CAST_INVALID_INPUT] '{s}' to boolean")
                valid[i] = False
        return CpuCol(dst, vals, valid)
    if isinstance(dst, (T.DateType, T.TimestampType)):
        vals = np.zeros(n, np.int64)
        for i, s in enumerate(c.values):
            if not valid[i]:
                continue
            r = _parse_dt_py(s, with_time=isinstance(dst, T.TimestampType))
            if r is None:
                if ansi:
                    raise SparkException(
                        f"[CAST_INVALID_INPUT] '{s}' to {dst!r}")
                valid[i] = False
            else:
                vals[i] = r
        np_dt = np.int32 if isinstance(dst, T.DateType) else np.int64
        return CpuCol(dst, vals.astype(np_dt), valid)
    raise NotImplementedError(f"cast string -> {dst!r}")


_JAVA_WS = "".join(chr(c) for c in range(33))


def _java_trim(s: str) -> str:
    """Java String/UTF8String trim: strip chars <= 0x20 on both ends."""
    return s.strip(_JAVA_WS)


def _parse_dt_py(s, with_time: bool):
    """Spark stringToDate/stringToTimestamp subset, matching the device
    kernel (cast_kernels._parse_ymd_hms): yyyy[-m[-d]] and
    yyyy-m-d[ |T]H:M:S[.ffffff], UTC."""
    import re
    import datetime
    if not isinstance(s, str):
        return None
    t = _java_trim(s)
    date_re = r"(\d{1,7})(?:-(\d{1,2})(?:-(\d{1,2}))?)?"
    time_re = r"(?:[ T](\d{1,2}):(\d{1,2}):(\d{1,2})(?:\.(\d+))?)?"
    m = re.fullmatch(date_re + (time_re if with_time else ""), t)
    if m is None:
        return None
    g = m.groups()
    y, mo, d = int(g[0]), int(g[1] or 1), int(g[2] or 1)
    try:
        date = datetime.date(y, mo, d)
    except ValueError:
        return None
    days = (date - datetime.date(1970, 1, 1)).days
    if not with_time:
        return days
    us = 0
    if g[3] is not None:
        H, Mi, S = int(g[3]), int(g[4]), int(g[5])
        if H > 23 or Mi > 59 or S > 59:
            return None
        frac = (g[6] or "")[:6].ljust(6, "0") if g[6] else "0"
        us = H * 3_600_000_000 + Mi * 60_000_000 + S * 1_000_000 + int(frac)
    return days * 86_400_000_000 + us


def _spark_float_str(v: float) -> str:
    """Java Double.toString-ish rendering (Spark cast double->string)."""
    if v != v:
        return "NaN"
    if v == float("inf"):
        return "Infinity"
    if v == float("-inf"):
        return "-Infinity"
    if v == int(v) and abs(v) < 1e16:
        return f"{int(v)}.0"
    return repr(v)


# ---------------------------------------------------------------------------
# String function breadth (reference stringFunctions.scala): all unary ops
# ride the vocab lift, so dict-encoded columns pay O(vocab) byte work.
# ---------------------------------------------------------------------------

def _row_of_byte(offsets, nbytes, cap):
    b = jnp.arange(nbytes, dtype=jnp.int32)
    return jnp.clip(jnp.searchsorted(offsets, b, side="right").astype(jnp.int32) - 1,
                    0, cap - 1)


def _slice_rows(raw, new_start, lens, cap):
    """Assemble a string column taking lens[i] bytes from new_start[i]."""
    new_off = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(lens).astype(jnp.int32)])
    nb = raw.shape[0]
    b = jnp.arange(nb, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(new_off, b, side="right").astype(jnp.int32) - 1,
                   0, cap - 1)
    src = jnp.clip(new_start[row] + (b - new_off[row]), 0, nb - 1)
    out = jnp.where(b < new_off[-1], raw[src], 0).astype(jnp.uint8)
    return {"offsets": new_off, "bytes": out}


class _TrimBase(Expression):
    """trim/ltrim/rtrim of ASCII spaces (Spark default trims ' ')."""

    lead = True
    tail = True

    def __init__(self, child):
        self.children = [child]

    def data_type(self):
        return T.STRING

    def with_children(self, children):
        return type(self)(children[0])

    def eval_tpu(self, ctx):
        c = self.children[0].eval_tpu(ctx)

        def compute(flat, cap):
            o = flat.data["offsets"]
            raw = flat.data["bytes"]
            nb = raw.shape[0]
            row = _row_of_byte(o, nb, cap)
            pos = jnp.arange(nb, dtype=jnp.int32)
            in_row = (pos >= o[row]) & (pos < o[row + 1])
            nonspace = in_row & (raw != 32)
            first_ns = jax.ops.segment_min(
                jnp.where(nonspace, pos, nb), row, num_segments=cap)
            last_ns = jax.ops.segment_max(
                jnp.where(nonspace, pos, -1), row, num_segments=cap)
            has = last_ns >= 0
            start = jnp.where(self.lead, jnp.where(has, first_ns, o[1:]),
                              o[:-1]).astype(jnp.int32)
            end = jnp.where(self.tail, jnp.where(has, last_ns + 1, start),
                            o[1:]).astype(jnp.int32)
            end = jnp.maximum(end, start)
            return ColumnVector(T.STRING,
                                _slice_rows(raw, start, end - start, cap), None)

        return _lift_unary(ctx, c, compute)

    def eval_cpu(self, cols, ansi=False):
        c = self.children[0].eval_cpu(cols, ansi)
        def f(s):
            if self.lead and self.tail:
                return s.strip(" ")
            return s.lstrip(" ") if self.lead else s.rstrip(" ")
        vals = np.array([f(s) if isinstance(s, str) else s for s in c.values],
                        object)
        return CpuCol(T.STRING, vals, c.valid)


class Trim(_TrimBase):
    lead = tail = True


class LTrim(_TrimBase):
    lead, tail = True, False


class RTrim(_TrimBase):
    lead, tail = False, True


class InitCap(Expression):
    """initcap: uppercase after a space / row start, lowercase elsewhere
    (ASCII mapping; reference documents the same non-ASCII incompat)."""

    def __init__(self, child):
        self.children = [child]

    def data_type(self):
        return T.STRING

    def with_children(self, children):
        return InitCap(children[0])

    def eval_tpu(self, ctx):
        c = self.children[0].eval_tpu(ctx)

        def compute(flat, cap):
            o = flat.data["offsets"]
            raw = flat.data["bytes"]
            nb = raw.shape[0]
            row = _row_of_byte(o, nb, cap)
            pos = jnp.arange(nb, dtype=jnp.int32)
            at_start = pos == o[row]
            prev = jnp.where(at_start, jnp.uint8(32), jnp.roll(raw, 1))
            after_sep = prev == 32
            lower = jnp.where((raw >= 65) & (raw <= 90), raw + 32, raw)
            upper = jnp.where((raw >= 97) & (raw <= 122), raw - 32, raw)
            out = jnp.where(after_sep, upper, lower)
            return ColumnVector(T.STRING, {"offsets": o, "bytes": out}, None)

        return _lift_unary(ctx, c, compute)

    def eval_cpu(self, cols, ansi=False):
        c = self.children[0].eval_cpu(cols, ansi)

        def f(s):
            return " ".join(w[:1].upper() + w[1:].lower() for w in s.split(" "))

        vals = np.array([f(s) if isinstance(s, str) else s for s in c.values],
                        object)
        return CpuCol(T.STRING, vals, c.valid)


class Ascii(Expression):
    """ascii(s): code of the first character (ASCII subset on device)."""

    def __init__(self, child):
        self.children = [child]

    def data_type(self):
        return T.INT32

    def with_children(self, children):
        return Ascii(children[0])

    def eval_tpu(self, ctx):
        c = self.children[0].eval_tpu(ctx)

        def compute(flat, cap):
            o = flat.data["offsets"]
            raw = flat.data["bytes"]
            nb = raw.shape[0]
            first = raw[jnp.clip(o[:-1], 0, nb - 1)].astype(jnp.int32)
            lens = o[1:] - o[:-1]
            return ColumnVector(T.INT32, jnp.where(lens > 0, first, 0), None)

        return _lift_unary(ctx, c, compute)

    def eval_cpu(self, cols, ansi=False):
        c = self.children[0].eval_cpu(cols, ansi)
        vals = np.array([ord(s[0]) if isinstance(s, str) and s else 0
                         for s in c.values], np.int32)
        return CpuCol(T.INT32, vals, c.valid)


class InStr(Expression):
    """instr(str, substr-literal): 1-based CHAR position of the first
    occurrence, 0 if absent."""

    def __init__(self, child, substr: str):
        self.children = [child]
        self.substr = substr

    def _params(self):
        return repr(self.substr)

    def data_type(self):
        return T.INT32

    def with_children(self, children):
        return InStr(children[0], self.substr)

    def eval_tpu(self, ctx):
        c = self.children[0].eval_tpu(ctx)
        pat = np.frombuffer(self.substr.encode("utf-8"), np.uint8)
        m = len(pat)

        def compute(flat, cap):
            o = flat.data["offsets"]
            raw = flat.data["bytes"]
            nb = raw.shape[0]
            if m == 0:
                return ColumnVector(T.INT32, jnp.ones(cap, jnp.int32), None)
            pos = jnp.arange(nb, dtype=jnp.int32)
            row = _row_of_byte(o, nb, cap)
            eq = jnp.ones(nb, jnp.bool_)
            for k in range(m):
                eq = eq & (raw[jnp.clip(pos + k, 0, nb - 1)] == pat[k])
            fits = (pos + m) <= o[row + 1]
            hit = eq & fits
            first_hit = jax.ops.segment_min(jnp.where(hit, pos, nb), row,
                                            num_segments=cap)
            found = first_hit < nb
            # byte position -> 1-based char index
            is_start = ((raw & 0xC0) != 0x80).astype(jnp.int32)
            csum = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                    jnp.cumsum(is_start)])
            char_idx = csum[jnp.clip(first_hit, 0, nb)] - csum[o[:-1]] + 1
            return ColumnVector(T.INT32,
                                jnp.where(found, char_idx, 0).astype(jnp.int32),
                                None)

        return _lift_unary(ctx, c, compute)

    def eval_cpu(self, cols, ansi=False):
        c = self.children[0].eval_cpu(cols, ansi)
        vals = np.array([s.find(self.substr) + 1 if isinstance(s, str) else 0
                         for s in c.values], np.int32)
        return CpuCol(T.INT32, vals, c.valid)


class StringRepeat(Expression):
    """repeat(str, n-literal)."""

    def __init__(self, child, n: int):
        self.children = [child]
        self.n = max(int(n), 0)

    def _params(self):
        return str(self.n)

    def data_type(self):
        return T.STRING

    def with_children(self, children):
        return StringRepeat(children[0], self.n)

    def eval_tpu(self, ctx):
        c = self.children[0].eval_tpu(ctx)
        n = self.n

        def compute(flat, cap):
            o = flat.data["offsets"]
            raw = flat.data["bytes"]
            nb = int(raw.shape[0])
            lens = o[1:] - o[:-1]
            out_lens = lens * n
            new_off = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                       jnp.cumsum(out_lens).astype(jnp.int32)])
            out_cap = nb * max(n, 1)
            b = jnp.arange(out_cap, dtype=jnp.int32)
            row = jnp.clip(jnp.searchsorted(new_off, b, side="right")
                           .astype(jnp.int32) - 1, 0, cap - 1)
            off_in = b - new_off[row]
            src = jnp.clip(o[row] + jnp.mod(off_in, jnp.maximum(lens[row], 1)),
                           0, nb - 1)
            out = jnp.where(b < new_off[-1], raw[src], 0).astype(jnp.uint8)
            return ColumnVector(T.STRING, {"offsets": new_off, "bytes": out},
                                None)

        return _lift_unary(ctx, c, compute)

    def eval_cpu(self, cols, ansi=False):
        c = self.children[0].eval_cpu(cols, ansi)
        vals = np.array([s * self.n if isinstance(s, str) else s
                         for s in c.values], object)
        return CpuCol(T.STRING, vals, c.valid)


# ---------------------------------------------------------------------------
# String breadth second tier: device-trivial length/slice family
# ---------------------------------------------------------------------------

class OctetLength(Expression):
    """octet_length(): UTF-8 byte count."""

    def __init__(self, child):
        self.children = [child]

    def data_type(self):
        return T.INT32

    def with_children(self, children):
        return OctetLength(children[0])

    def eval_tpu(self, ctx):
        c = self.children[0].eval_tpu(ctx)

        def compute(flat, cap):
            off = flat.data["offsets"]
            lens = (off[1: cap + 1] - off[:cap]).astype(jnp.int32)
            return ColumnVector(T.INT32, lens, None)

        return _lift_unary(ctx, c, compute)

    def eval_cpu(self, cols, ansi=False):
        c = self.children[0].eval_cpu(cols, ansi)
        vals = np.array([len(s.encode()) if isinstance(s, str) else 0
                         for s in c.values], np.int32)
        return CpuCol(T.INT32, vals, c.valid)


class BitLength(OctetLength):
    """bit_length(): 8 * octet_length."""

    def with_children(self, children):
        return BitLength(children[0])

    def eval_tpu(self, ctx):
        base = super().eval_tpu(ctx)
        return ColumnVector(T.INT32, base.data * 8, base.validity)

    def eval_cpu(self, cols, ansi=False):
        base = super().eval_cpu(cols, ansi)
        return CpuCol(T.INT32, base.values * 8, base.valid)


class Left(Substring):
    """left(s, n) = substring(s, 1, n); n < 0 yields ''."""

    def __init__(self, child, n: int):
        super().__init__(child, 1, max(int(n), 0))

    def with_children(self, children):
        return Left(children[0], self.length)


class Right(Expression):
    """right(s, n): last n characters ('' for n <= 0)."""

    def __init__(self, child, n: int):
        self.children = [child]
        self.n = int(n)

    def _params(self):
        return str(self.n)

    def with_children(self, children):
        return Right(children[0], self.n)

    def data_type(self):
        return T.STRING

    def eval_tpu(self, ctx):
        if self.n <= 0:
            inner = Substring(self.children[0], 1, 0)
        else:
            inner = Substring(self.children[0], -self.n, self.n)
        inner = inner.with_children([self.children[0]])
        return inner.eval_tpu(ctx)

    def eval_cpu(self, cols, ansi=False):
        c = self.children[0].eval_cpu(cols, ansi)
        n = self.n
        vals = np.array([s[-n:] if isinstance(s, str) and n > 0 else
                         ("" if isinstance(s, str) else None)
                         for s in c.values], object)
        return CpuCol(T.STRING, vals, c.valid)


class Chr(Expression):
    """chr(n): the character with code n % 256 for positive n in Latin-1
    range (Spark semantics: n <= 0 -> '', 256-multiples -> '\\0' etc.)."""

    def __init__(self, child):
        self.children = [child]

    def data_type(self):
        return T.STRING

    def with_children(self, children):
        return Chr(children[0])

    def eval_tpu(self, ctx):
        c = self.children[0].eval_tpu(ctx)
        v = c.data.astype(jnp.int64)
        code = jnp.where(v < 0, jnp.int64(0), v % 256)
        # UTF-8: codes < 128 are one byte; 128..255 encode as two bytes.
        # Spark: only NEGATIVE n gives ''; chr(0) and chr(256) are '\\x00'
        two = code >= 128
        lens = jnp.where(c.validity_or_default(ctx.num_rows) & (v >= 0),
                         jnp.where(two, 2, 1), 0).astype(jnp.int32)
        off = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(lens).astype(jnp.int32)])
        cap = ctx.capacity
        bcap = 2 * cap
        b = jnp.arange(bcap, dtype=jnp.int32)
        row = jnp.clip(jnp.searchsorted(off, b, side="right").astype(jnp.int32)
                       - 1, 0, cap - 1)
        in_r = b < off[-1]
        second = b - off[row] == 1
        cd = code[row]
        byte1 = jnp.where(cd < 128, cd, 0xC0 | (cd >> 6))
        byte2 = 0x80 | (cd & 0x3F)
        ob = jnp.where(second, byte2, byte1)
        out_bytes = jnp.where(in_r, ob, 0).astype(jnp.uint8)
        return ColumnVector(T.STRING, {"offsets": off, "bytes": out_bytes},
                            _valid_of(c, ctx))

    def eval_cpu(self, cols, ansi=False):
        c = self.children[0].eval_cpu(cols, ansi)
        out = []
        for v, ok in zip(c.values, c.valid):
            if not ok:
                out.append(None)
                continue
            n = int(v)
            out.append("" if n < 0 else chr(n % 256))
        return CpuCol(T.STRING, np.array(out, object), c.valid)
