"""Expression evaluation over device batches.

Reference analogue: `colexec/evalExpression.go` + the function kernels it
dispatches to (`plan/function`, `vectorize/`, cgo XCall). Here the whole
bound-expression tree evaluates inside one traced JAX computation, so XLA
fuses the entire WHERE clause (or projection list) into a single kernel
over the batch.

Varchar columns arrive as dictionary codes + a host-side dictionary
(ExecBatch.dicts): string predicates are evaluated on the *dictionary*
(host, tiny) and become code-space operations on device — `eq` is a code
compare, LIKE is a host regex over distinct values turned into a boolean
LUT gather.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import math as _math
import re
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from matrixone_tpu.container import dtypes as dt
from matrixone_tpu.container.device import DeviceBatch, DeviceColumn
from matrixone_tpu.container.dtypes import DType, TypeOid
from matrixone_tpu.ops import distance as D, scalar as S
from matrixone_tpu.sql.expr import (BoundCase, BoundCast, BoundCol,
                                    BoundExpr, BoundFunc, BoundInList,
                                    BoundIsNull, BoundLike, BoundLiteral,
                                    BoundUdfCall)


@dataclasses.dataclass
class ExecBatch:
    """A batch mid-pipeline: device columns + host dictionaries + row mask.

    `mask` folds the batch row_mask with every filter applied so far —
    operators consume masks instead of compacting (ops/filter.py rationale).
    """
    batch: DeviceBatch
    dicts: Dict[str, List[str]]
    mask: jnp.ndarray
    #: column -> (lo, hi) of its non-NULL integer values, where a producer
    #: has observed them (the fused join, of its build side's columns): to
    #: an integer column what `dicts` is to a string column, a bounded
    #: code space (the grouped aggregate's wide dense path reads it)
    ranges: Dict[str, tuple] = dataclasses.field(default_factory=dict)

    @property
    def padded_len(self) -> int:
        # the mask always has the true padded length — batch.padded_len
        # degenerates to 1 when every column is a const (literal-only
        # projections)
        return self.mask.shape[0]


class EvalError(ValueError):
    pass


#: trace-time literal lifting (vm/fusion.py): inside a fused-fragment
#: trace, selected numeric literals evaluate to traced input scalars
#: instead of baked constants, so one compiled program serves every
#: parameter value of the same plan shape.  The binding is thread-local
#: and only ever active while a fragment trace is being built.
_LIFT_TLS = __import__("threading").local()


class lifted_literal_scope:
    """Bind {id(BoundLiteral): traced 0-d array} for the duration of a
    fragment trace; nests (the previous map is restored on exit)."""

    def __init__(self, mapping: Dict[int, object]):
        self._mapping = mapping

    def __enter__(self):
        self._prev = getattr(_LIFT_TLS, "map", None)
        _LIFT_TLS.map = self._mapping
        return self

    def __exit__(self, *exc):
        _LIFT_TLS.map = self._prev
        return False


def _is_varchar(dtype: DType) -> bool:
    return dtype.is_varlen


def _dict_of(e: BoundExpr, ex: ExecBatch) -> Optional[List[str]]:
    """Dictionary of a varchar-valued expression (recursive: columns,
    string-function results, CASE over string literals)."""
    if isinstance(e, BoundCol):
        return ex.dicts.get(e.name)
    if isinstance(e, BoundCase) and e.dtype.is_varlen:
        return case_string_dict(e)
    if isinstance(e, BoundFunc) and e.op == "monthname":
        return list(_MONTH_NAMES)
    if isinstance(e, BoundFunc) and e.op == "dayname":
        return list(_DAY_NAMES)
    if isinstance(e, BoundFunc) and e.dtype.is_varlen \
            and e.op in _STRING_FUNCS:
        return string_func_final_dict(e, ex)
    if isinstance(e, BoundFunc) and e.op in _NUM2STR_FUNCS:
        return num2str_final_dict(e, ex)
    if isinstance(e, BoundFunc) and e.op == "uuid":
        return uuid_dict(ex)
    return None


def eval_expr(e: BoundExpr, ex: ExecBatch) -> DeviceColumn:
    if isinstance(e, BoundCol):
        return ex.batch.columns[e.name]
    if isinstance(e, BoundLiteral):
        lifted = getattr(_LIFT_TLS, "map", None)
        if lifted is not None:
            v = lifted.get(id(e))
            if v is not None:
                # fused-fragment trace: the literal is a traced input
                return DeviceColumn(jnp.reshape(v, (1,)),
                                    jnp.ones((1,), jnp.bool_), e.dtype)
        if e.value is None:
            return DeviceColumn.const_null(e.dtype)
        if e.dtype.is_vector:
            data = jnp.asarray([e.value], dtype=e.dtype.jnp_dtype)
            return DeviceColumn(data, jnp.ones((1,), jnp.bool_), e.dtype)
        if _is_varchar(e.dtype):
            # const string column: code 0 into a single-entry dictionary
            # (the projection attaches the dict via expr_output_dict)
            col = DeviceColumn.const(0, dt.INT32)
            return DeviceColumn(col.data, col.validity, e.dtype)
        return DeviceColumn.const(e.value, e.dtype)
    if isinstance(e, BoundCast):
        return S.cast(eval_expr(e.arg, ex), e.dtype)
    if isinstance(e, BoundIsNull):
        col = eval_expr(e.arg, ex)
        out = S.isnotnull(col) if e.negated else S.isnull(col)
        return out
    if isinstance(e, BoundCase):
        if _is_varchar(e.dtype):
            return _eval_case_strings(e, ex)
        # every branch coerces to the CASE's bound result type BEFORE
        # the select: mixed int/double/decimal branches otherwise flow
        # raw through jnp.where under the first branch's dtype tag —
        # scaled decimal ints mix with floats, downstream arithmetic
        # casts by the wrong claimed type (moqa seed-1 findings)
        else_col = (S.cast(eval_expr(e.else_, ex), e.dtype)
                    if e.else_ is not None
                    else DeviceColumn.const_null(e.dtype))
        out = else_col
        for cond, val in reversed(e.whens):
            out = S.case_when(eval_expr(cond, ex),
                              S.cast(eval_expr(val, ex), e.dtype), out)
        return out
    if isinstance(e, BoundInList):
        arg = eval_expr(e.arg, ex)
        d = _dict_of(e.arg, ex)
        if d is not None:
            code_of = {s: i for i, s in enumerate(d)}
            codes = [code_of[v] for v in e.values if v in code_of]
            if not codes:
                base = DeviceColumn(jnp.zeros(arg.data.shape, jnp.bool_),
                                    arg.validity, dt.BOOL)
            else:
                base = S.in_list(arg, codes)
        else:
            base = S.in_list(arg, list(e.values))
        return S.logical_not(base) if e.negated else base
    if isinstance(e, BoundLike):
        arg = eval_expr(e.arg, ex)
        d = _dict_of(e.arg, ex)
        if d is None:
            raise EvalError("LIKE requires a varchar column")
        rx = _like_regex(e.pattern)
        lut = np.array([bool(rx.match(s)) for s in d], dtype=np.bool_)
        if e.negated:
            lut = ~lut
        hit = jnp.asarray(lut)[jnp.clip(arg.data, 0, len(d) - 1)]
        return DeviceColumn(hit, arg.validity, dt.BOOL)
    if isinstance(e, BoundUdfCall):
        from matrixone_tpu.udf.executor import eval_udf_call
        return eval_udf_call(e, ex)
    if isinstance(e, BoundFunc):
        return _eval_func(e, ex)
    raise EvalError(f"unsupported expression {type(e).__name__}")


_STRING_FUNCS = {"upper", "lower", "length", "reverse", "trim", "ltrim",
                 "rtrim", "concat", "substring", "replace", "starts_with",
                 "ends_with",
                 # long tail (VERDICT r3 directive 6): dictionary-level
                 # Python semantics, device gather on codes — O(uniques)
                 # host work per batch, never O(rows)
                 "lpad", "rpad", "repeat", "instr", "locate", "ascii",
                 "bit_length", "hex", "unhex", "md5", "sha1", "sha2",
                 "crc32", "to_base64", "from_base64", "substring_index",
                 "field", "find_in_set", "strcmp", "space", "soundex",
                 "quote", "bin", "oct", "conv",
                 "regexp_like", "regexp_instr", "regexp_substr",
                 "regexp_replace",
                 "json_extract", "json_unquote", "json_valid",
                 "json_length", "json_type", "json_keys",
                 # index-less MATCH AGAINST fallback (WHERE truthiness /
                 # un-indexed scans): tf of query terms per dictionary
                 # entry — the BM25-ranked path is the fulltext INDEX
                 # rewrite (vm/fulltext_scan.py)
                 "match_against",
                 # geo over WKT strings (reference: pkg/geo) — planar
                 # semantics evaluated on the dictionary (matrixone_tpu.geo)
                 "st_geomfromtext", "st_astext", "st_x", "st_y",
                 "st_distance", "st_within", "st_contains", "st_area",
                 "st_geohash",
                 # r5 long tail (function_id.go families)
                 "left", "right", "ord", "insert_str", "elt",
                 "concat_ws", "split_part", "octet_length", "inet_aton",
                 "str_to_date", "time_to_sec",
                 # r6 long tail: net/json/time-string families
                 "is_ipv4", "is_ipv6", "inet6_aton", "inet6_ntoa",
                 "json_quote", "json_contains",
                 "timediff", "addtime", "subtime", "time_format",
                 # LLM: one endpoint call per DISTINCT value
                 "llm_chat"}

#: numeric input -> string output: evaluated over the column's UNIQUE
#: values host-side (O(distinct)), gathered on device — the same
#: cost model as the dictionary-level string functions
_NUM2STR_FUNCS = {"date_format", "sec_to_time", "inet_ntoa",
                  "format_num", "hex_int",
                  # r6: bit-set and byte presentations of a numeric col
                  "char_fn", "make_set", "export_set", "maketime"}


#: marks the COLUMN's position in a string call's literal list — distinct
#: from None, which is a genuine NULL literal argument
_COLPOS = object()


def _string_arg_info(e, ex, want_col: bool = True):
    """-> (col DeviceColumn|None, dict, literals list) for a string
    function call: at most one dict-coded column operand; an all-literal
    call treats the first literal as the subject. want_col=False skips the
    device evaluation (dictionary derivation only)."""
    col = None
    col_ast = None
    d = None
    lits = []
    for a in e.args:
        if isinstance(a, BoundLiteral):
            v = a.value
            if v is not None and a.dtype.oid == dt.TypeOid.DECIMAL64:
                v = v / 10 ** a.dtype.scale   # surface the REAL value,
            lits.append(v)                    # never the scaled integer
            continue
        src = _dict_of(a, ex)
        if src is None:
            raise EvalError(
                f"string function {e.op} needs a varchar column or literal "
                f"arguments")
        if col_ast is not None:
            raise EvalError(
                f"string function {e.op} over two columns not supported yet")
        col_ast = a
        d = src
        lits.append(_COLPOS)       # placeholder for the column position
    if col_ast is None:
        # all-literal call: the first literal is the subject string. A
        # NULL subject stays None in lits so the NULL-propagation rule
        # fires (left(NULL, 2) is NULL, not '')
        if not lits:
            raise EvalError(f"string function {e.op} needs arguments")
        d = [str(lits[0]) if lits[0] is not None else ""]
        if lits[0] is not None:
            lits[0] = _COLPOS
    elif want_col:
        col = eval_expr(col_ast, ex)
    return col, d, lits


def _json_parse(s):
    import json as _json
    try:
        return _json.loads(s)
    except (ValueError, TypeError):
        return _JSON_BAD


_JSON_BAD = object()


def _json_path(doc, path: str):
    """$.a.b[0] subset of MySQL JSON paths; returns _JSON_BAD on miss
    AND on any path syntax outside the subset (never a silent partial
    parse that extracts from the wrong place)."""
    import re as _re
    if not _re.fullmatch(r"\$(?:\.[A-Za-z_][A-Za-z_0-9]*|\[\d+\])*",
                         path):
        return _JSON_BAD
    cur = doc
    for m in _re.finditer(r"\.([A-Za-z_][A-Za-z_0-9]*)|\[(\d+)\]",
                          path[1:]):
        key, idx = m.group(1), m.group(2)
        if key is not None:
            if not isinstance(cur, dict) or key not in cur:
                return _JSON_BAD
            cur = cur[key]
        else:
            i = int(idx)
            if not isinstance(cur, list) or i >= len(cur):
                return _JSON_BAD
            cur = cur[i]
    return cur


def _parse_time_str(s: str):
    """'[-]H+:MM:SS' (MySQL TIME text, hours may exceed 23) -> signed
    seconds, or None on malformed input."""
    import re as _re
    m = _re.fullmatch(r"(-?)(\d{1,3}):([0-5]?\d):([0-5]?\d)(?:\.\d+)?",
                      s.strip())
    if m is None:
        return None
    sec = int(m.group(2)) * 3600 + int(m.group(3)) * 60 + int(m.group(4))
    return -sec if m.group(1) else sec


def _fmt_time(sec: int) -> str:
    sign = "-" if sec < 0 else ""
    sec = abs(sec)
    return f"{sign}{sec // 3600:02d}:{sec % 3600 // 60:02d}:{sec % 60:02d}"


def _is_ipv6_text(s: str) -> bool:
    import ipaddress
    try:
        return isinstance(ipaddress.ip_address(s.strip()),
                          ipaddress.IPv6Address)
    except ValueError:
        return False


def _soundex(s: str) -> str:
    codes = {**dict.fromkeys("BFPV", "1"), **dict.fromkeys("CGJKQSXZ", "2"),
             **dict.fromkeys("DT", "3"), "L": "4",
             **dict.fromkeys("MN", "5"), "R": "6"}
    s = "".join(c for c in s.upper() if c.isalpha())
    if not s:
        return ""
    out = s[0]
    prev = codes.get(s[0], "")
    for c in s[1:]:
        code = codes.get(c, "")
        if code and code != prev:
            out += code
        if c not in "HW":
            prev = code
    return (out + "000")[:4]


def _apply_string_func(op, s, lits):
    """Python-level semantics per dictionary entry (MySQL behavior).
    Returns None for SQL NULL results (invalid input etc.)."""
    import base64
    import hashlib
    import re as _re
    import zlib

    def args():
        return [x for x in lits if x is not _COLPOS]

    def at(i, default=None):
        """Positional arg: the dictionary entry if the column sits at
        position i, else the literal there."""
        if i >= len(lits):
            return default
        return s if lits[i] is _COLPOS else lits[i]

    # MySQL: a NULL argument yields NULL — except functions with
    # explicit NULL semantics (concat_ws skips NULLs; elt/coalesce
    # handle them positionally)
    if op not in ("concat_ws", "elt") and any(x is None for x in lits):
        return None

    if op == "is_ipv4":
        parts = at(0, "").split(".")
        return len(parts) == 4 and all(
            p.isdigit() and len(p) <= 3 and int(p) <= 255 for p in parts)
    if op == "is_ipv6":
        return _is_ipv6_text(at(0, ""))
    if op == "inet6_aton":
        # MySQL returns VARBINARY(16); surfaced here as its hex text
        # (the engine has no binary type — hex() of the reference value)
        import ipaddress
        try:
            return ipaddress.ip_address(at(0, "").strip()).packed.hex()
        except ValueError:
            return None
    if op == "inet6_ntoa":
        import ipaddress
        try:
            raw = bytes.fromhex(at(0, ""))
            if len(raw) not in (4, 16):
                return None
            return str(ipaddress.ip_address(raw))
        except ValueError:
            return None
    if op == "json_quote":
        import json as _json
        return _json.dumps(str(at(0, "")))
    if op == "json_contains":
        import json as _json
        doc = _json_parse(at(0, ""))
        cand = _json_parse(at(1, ""))
        if doc is _JSON_BAD or cand is _JSON_BAD:
            return None

        def contains(d, c):
            # MySQL: a candidate ARRAY is contained in a target array
            # iff EVERY candidate element is contained in SOME element
            # of the target; a non-array candidate iff some target
            # element contains it
            if isinstance(d, list):
                if isinstance(c, list):
                    return all(any(contains(x, y) for x in d) for y in c)
                return any(contains(x, c) for x in d)
            if isinstance(d, dict) and isinstance(c, dict):
                return all(k in d and contains(d[k], v)
                           for k, v in c.items())
            if isinstance(d, bool) != isinstance(c, bool):
                return False        # MySQL: true != 1 in JSON
            return d == c
        return bool(contains(doc, cand))
    if op == "timediff":
        a, b = _parse_time_str(at(0, "")), _parse_time_str(at(1, ""))
        if a is None or b is None:
            return None
        return _fmt_time(a - b)
    if op in ("addtime", "subtime"):
        a, b = _parse_time_str(at(0, "")), _parse_time_str(at(1, ""))
        if a is None or b is None:
            return None
        return _fmt_time(a + b if op == "addtime" else a - b)
    if op == "time_format":
        sec = _parse_time_str(at(0, ""))
        fmt = at(1, "%H:%i:%s")
        if sec is None or fmt is None:
            return None
        sign = "-" if sec < 0 else ""
        sec = abs(sec)
        h, mi, ss = sec // 3600, sec % 3600 // 60, sec % 60
        out, i = [], 0
        while i < len(fmt):
            if fmt[i] == "%" and i + 1 < len(fmt):
                c = fmt[i + 1]
                i += 2
                if c == "H":
                    out.append(f"{sign}{h:02d}")
                elif c == "k":
                    out.append(f"{sign}{h}")
                elif c == "h" or c == "I":
                    out.append(f"{(h % 12) or 12:02d}")
                elif c == "i":
                    out.append(f"{mi:02d}")
                elif c == "s" or c == "S":
                    out.append(f"{ss:02d}")
                elif c == "p":
                    out.append("AM" if (h % 24) < 12 else "PM")
                elif c == "T":
                    out.append(f"{sign}{h:02d}:{mi:02d}:{ss:02d}")
                else:
                    out.append(c)
            else:
                out.append(fmt[i])
                i += 1
        return "".join(out)
    if op == "upper":
        return s.upper()
    if op == "lower":
        return s.lower()
    if op == "length":
        return len(s.encode())
    if op == "bit_length":
        return len(s.encode()) * 8
    if op == "ascii":
        return ord(s[0]) if s else 0
    if op == "reverse":
        return s[::-1]
    if op == "trim":
        return s.strip()
    if op == "ltrim":
        return s.lstrip()
    if op == "rtrim":
        return s.rstrip()
    if op == "concat":
        return "".join(s if x is _COLPOS else str(x) for x in lits)
    if op == "substring":
        a = args()
        start = int(a[0])
        start = start - 1 if start > 0 else len(s) + start
        if len(a) > 1:
            return s[start:start + int(a[1])]
        return s[start:]
    if op == "replace":
        a = args()
        return s.replace(str(a[0]), str(a[1]))
    if op == "starts_with":
        return s.startswith(str(args()[0]))
    if op == "ends_with":
        return s.endswith(str(args()[0]))
    if op == "lpad":
        a = args()
        n, pad = int(a[0]), str(a[1]) if len(a) > 1 else " "
        if n <= len(s):
            return s[:n]
        if not pad:
            return ""        # MySQL: cannot fill with an empty pad
        return (pad * n)[:n - len(s)] + s
    if op == "rpad":
        a = args()
        n, pad = int(a[0]), str(a[1]) if len(a) > 1 else " "
        if n <= len(s):
            return s[:n]
        if not pad:
            return ""
        return s + (pad * n)[:n - len(s)]
    if op == "repeat":
        n = int(args()[0])
        return s * max(n, 0)
    if op == "left":
        return s[:max(int(args()[0]), 0)]
    if op == "right":
        n = max(int(args()[0]), 0)
        return s[max(len(s) - n, 0):] if n else ""
    if op == "ord":
        # MySQL ORD: leftmost character's byte sequence as an int
        if not s:
            return 0
        out = 0
        for byte in s[0].encode():
            out = out * 256 + byte
        return out
    if op == "octet_length":
        return len(s.encode())
    if op == "insert_str":
        a = args()
        pos, ln, news = int(a[0]), int(a[1]), str(a[2])
        if pos < 1 or pos > len(s):
            return s
        return s[:pos - 1] + news + s[pos - 1 + max(ln, 0):]
    if op == "elt":
        idx = at(0)
        if idx is None:
            return None
        i = int(idx)
        options = [s if x is _COLPOS else
                   (None if x is None else str(x)) for x in lits[1:]]
        if i < 1 or i > len(options):
            return None
        return options[i - 1]
    if op == "concat_ws":
        sep = at(0)
        if sep is None:
            return None                   # NULL separator -> NULL
        parts = [s if x is _COLPOS else str(x)
                 for x in lits[1:] if x is not None]   # NULLs skipped
        return str(sep).join(parts)
    if op == "split_part":
        a = args()
        parts = s.split(str(a[0]))
        i = int(a[1])
        if i < 1 or i > len(parts):
            return None
        return parts[i - 1]
    if op == "inet_aton":
        try:
            p = s.split(".")
            if len(p) != 4 or any(not x.isdigit() or int(x) > 255
                                  for x in p):
                return None
            return (int(p[0]) << 24 | int(p[1]) << 16
                    | int(p[2]) << 8 | int(p[3]))
        except ValueError:
            return None
    if op == "str_to_date":
        import datetime as _dtm
        fmt = str(args()[0])
        pyfmt = (fmt.replace("%i", "%M").replace("%s", "%S")
                 .replace("%e", "%d").replace("%c", "%m"))
        try:
            d0 = _dtm.datetime.strptime(s, pyfmt).date()
            return (d0 - _dtm.date(1970, 1, 1)).days
        except ValueError:
            return None
    if op == "llm_chat":
        from matrixone_tpu import llm as _llm
        from matrixone_tpu.frontend.session import current_session
        sess = current_session()
        return _llm.chat(s, sess.variables if sess else None)
    if op == "time_to_sec":
        try:
            t = s.strip()
            neg = t.startswith("-")
            if neg:
                t = t[1:]
            hh, mm, ss = (t.split(":") + ["0", "0"])[:3]
            total = int(hh) * 3600 + int(mm) * 60 + int(float(ss))
            return -total if neg else total
        except ValueError:
            return None
    if op == "space":
        return " " * max(int(s), 0)
    if op == "instr":
        return str(at(0, "")).find(str(at(1, ""))) + 1
    if op == "locate":
        sub, subj = str(at(0, "")), str(at(1, ""))
        pos = int(at(2, 1))
        return subj.find(sub, max(pos - 1, 0)) + 1
    if op == "substring_index":
        a = args()
        delim, count = str(a[0]), int(a[1])
        if not delim:
            return ""
        parts = s.split(delim)
        if count > 0:
            return delim.join(parts[:count])
        if count < 0:
            return delim.join(parts[count:])
        return ""
    if op == "field":
        # the column may sit at ANY position: substitute the dictionary
        # entry at its placeholder before comparing
        full = [s if x is _COLPOS else str(x) for x in lits]
        try:
            return full[1:].index(full[0]) + 1
        except ValueError:
            return 0
    if op == "find_in_set":
        target, setstr = str(at(0, "")), str(at(1, ""))
        if not setstr:
            return 0
        items = setstr.split(",")
        try:
            return items.index(target) + 1
        except ValueError:
            return 0
    if op == "strcmp":
        a0, a1 = str(at(0, "")), str(at(1, ""))
        return -1 if a0 < a1 else (1 if a0 > a1 else 0)
    if op == "hex":
        return s.encode().hex().upper()
    if op == "unhex":
        try:
            return bytes.fromhex(s).decode("utf-8", errors="strict")
        except ValueError:
            return None
    if op == "md5":
        return hashlib.md5(s.encode()).hexdigest()
    if op == "sha1":
        return hashlib.sha1(s.encode()).hexdigest()
    if op == "sha2":
        bits = int(args()[0]) if args() else 256
        fn = {224: hashlib.sha224, 256: hashlib.sha256,
              384: hashlib.sha384, 512: hashlib.sha512,
              0: hashlib.sha256}.get(bits)
        return fn(s.encode()).hexdigest() if fn else None
    if op == "crc32":
        return zlib.crc32(s.encode())
    if op == "to_base64":
        return base64.b64encode(s.encode()).decode()
    if op == "from_base64":
        try:
            return base64.b64decode(s.encode(), validate=True).decode(
                "utf-8", errors="strict")
        except (ValueError, UnicodeDecodeError):
            return None
    if op == "soundex":
        return _soundex(s)
    if op == "quote":
        body = s.replace("\\", "\\\\").replace("'", "\\'")
        return f"'{body}'"
    if op in ("bin", "oct", "conv"):
        try:
            v = int(str(at(0, s)), 10 if op != "conv"
                    else int(args()[0]))
        except ValueError:
            return None
        if v < 0:
            # MySQL treats negatives as unsigned 64-bit two's complement
            v &= 0xFFFFFFFFFFFFFFFF
        if op == "bin":
            return format(v, "b")
        if op == "oct":
            return format(v, "o")
        to = int(args()[1])
        if not (2 <= to <= 36):
            return None
        digits = "0123456789abcdefghijklmnopqrstuvwxyz"
        out = ""
        while v:
            out = digits[v % to] + out
            v //= to
        return (out or "0").upper()
    if op == "regexp_like":
        return bool(_re.search(str(args()[0]), s))
    if op == "regexp_instr":
        m = _re.search(str(args()[0]), s)
        return (m.start() + 1) if m else 0
    if op == "regexp_substr":
        m = _re.search(str(args()[0]), s)
        return m.group(0) if m else None
    if op == "regexp_replace":
        a = args()
        return _re.sub(str(a[0]), str(a[1]), s)
    if op.startswith("st_"):
        from matrixone_tpu import geo as G
        if op == "st_geohash":
            g = G.parse_wkt(s)
            if g is None or g.kind != "POINT":
                return None
            prec = int(args()[0]) if args() else 12
            return G.geohash(g.coords[0][0], g.coords[0][1],
                             max(1, min(prec, 12)))
        if op in ("st_distance", "st_within", "st_contains"):
            g1 = G.parse_wkt(str(at(0, "")))
            g2 = G.parse_wkt(str(at(1, "")))
            if g1 is None or g2 is None:
                return None
            if op == "st_distance":
                return G.distance(g1, g2)
            if op == "st_within":
                return G.contains(g2, g1)
            return G.contains(g1, g2)
        g = G.parse_wkt(s)
        if g is None:
            return None
        if op in ("st_geomfromtext", "st_astext"):
            return g.wkt()
        if op == "st_x":
            return g.coords[0][0] if g.kind == "POINT" else None
        if op == "st_y":
            return g.coords[0][1] if g.kind == "POINT" else None
        if op == "st_area":
            return G.area(g)
    if op == "match_against":
        from matrixone_tpu.fulltext import tokenize as _ft_tokenize
        terms = set(_ft_tokenize(str(args()[0])))
        if not terms:
            return 0.0
        toks = _ft_tokenize(s)
        return float(sum(1 for t in toks if t in terms))
    if op.startswith("json_"):
        import json as _json
        doc = _json_parse(s)
        if op == "json_valid":
            return doc is not _JSON_BAD
        if doc is _JSON_BAD:
            return None
        if op == "json_extract":
            got = _json_path(doc, str(args()[0]))
            return None if got is _JSON_BAD else _json.dumps(
                got, separators=(", ", ": "), ensure_ascii=False)
        if op == "json_unquote":
            if isinstance(doc, str):
                return doc
            return s
        if op == "json_length":
            path = args()
            tgt = doc if not path else _json_path(doc, str(path[0]))
            if tgt is _JSON_BAD:
                return None
            return len(tgt) if isinstance(tgt, (list, dict)) else 1
        if op == "json_type":
            tgt = doc
            if args():
                tgt = _json_path(doc, str(args()[0]))
                if tgt is _JSON_BAD:
                    return None
            if isinstance(tgt, bool):
                return "BOOLEAN"
            if tgt is None:
                return "NULL"
            if isinstance(tgt, int):
                return "INTEGER"
            if isinstance(tgt, float):
                return "DOUBLE"
            if isinstance(tgt, str):
                return "STRING"
            return "ARRAY" if isinstance(tgt, list) else "OBJECT"
        if op == "json_keys":
            if not isinstance(doc, dict):
                return None
            return _json.dumps(list(doc.keys()), ensure_ascii=False)
    raise EvalError(op)


def string_func_output_dict(e: BoundFunc, ex: ExecBatch):
    """Transformed dictionary for a varchar-result string function
    (no device work: dictionaries + literals only). Entries may be None
    (SQL NULL results, e.g. unhex of garbage)."""
    _, d, lits = _string_arg_info(e, ex, want_col=False)
    return [_apply_string_func(e.op, s, lits) for s in d]


#: MySQL date_format codes -> strftime (%e/%c handled inline: no-pad
#: forms are platform-dependent in strftime)
_MYSQL_FMT = {
    "%Y": "%Y", "%y": "%y", "%m": "%m", "%d": "%d", "%H": "%H",
    "%h": "%I", "%i": "%M", "%s": "%S", "%f": "%f", "%M": "%B",
    "%b": "%b", "%W": "%A", "%a": "%a", "%j": "%j", "%p": "%p",
    "%T": "%H:%M:%S", "%r": "%I:%M:%S %p", "%%": "%%",
}


def _round_bigint(v, dtype) -> int:
    """MySQL: round a numeric argument to BIGINT. Integers must NOT
    round-trip through float (2^53 truncates the low bits of a BIGINT);
    decimals round half-away-from-zero in the exact scaled-integer
    domain; floats round half-away-from-zero (Python round() is
    banker's: hex(254.5) would give 'FE')."""
    if dtype is not None and dtype.oid == dt.TypeOid.DECIMAL64:
        scale = 10 ** dtype.scale
        sv = int(v)
        q, r = divmod(abs(sv), scale)
        if 2 * r >= scale:
            q += 1
        return -q if sv < 0 else q
    if isinstance(v, (int, np.integer)) or (
            dtype is not None and dtype.is_integer):
        return int(v)
    x = float(v)
    n = _math.floor(abs(x) + 0.5)
    return -n if x < 0 else n


def _num2str_value(op, v, lits, dtype) -> "Optional[str]":
    """One unique input value -> output string (None = SQL NULL)."""
    import datetime as _dtm
    if op == "hex_int":
        n = _round_bigint(v, dtype)
        if n < 0:                        # unsigned 64-bit view (MySQL)
            n &= 0xFFFFFFFFFFFFFFFF
        return format(n, "X")
    if op == "inet_ntoa":
        n = int(v)
        if n < 0 or n > 0xFFFFFFFF:
            return None
        return ".".join(str((n >> s) & 0xFF) for s in (24, 16, 8, 0))
    if op == "sec_to_time":
        n = int(v)
        sign = "-" if n < 0 else ""
        n = abs(n)
        return f"{sign}{n // 3600:02d}:{n % 3600 // 60:02d}:{n % 60:02d}"
    if op == "format_num":
        nd = int(lits[1]) if len(lits) > 1 and lits[1] is not None else 0
        x = float(v)
        if dtype is not None and dtype.oid == dt.TypeOid.DECIMAL64:
            x = x / 10 ** dtype.scale      # stored scaled (exact int)
        return f"{x:,.{max(nd, 0)}f}"
    if op == "char_fn":
        n = _round_bigint(v, dtype)
        if n < 0:
            return None
        bs = n.to_bytes(max((n.bit_length() + 7) // 8, 1), "big")
        return bs.decode("utf-8", "replace")
    if op == "make_set":
        # NULL strings are skipped (MySQL), but the bit mask rounds
        bits = _round_bigint(v, dtype)
        out = [str(s) for i, s in enumerate(lits[1:])
               if s is not None and bits & (1 << i)]
        return ",".join(out)
    if op == "export_set":
        # MySQL: a NULL on/off/separator/count argument -> NULL result
        if any(x is None for x in lits[1:5]):
            return None
        bits = _round_bigint(v, dtype)
        on = str(lits[1]) if len(lits) > 1 else "1"
        off = str(lits[2]) if len(lits) > 2 else "0"
        sep = str(lits[3]) if len(lits) > 3 else ","
        width = _round_bigint(lits[4], None) if len(lits) > 4 else 64
        return sep.join(on if bits & (1 << i) else off
                        for i in range(max(0, min(width, 64))))
    if op == "maketime":
        h = _round_bigint(v, dtype)
        m = (_round_bigint(lits[1], None)
             if len(lits) > 1 and lits[1] is not None else -1)
        s = (_round_bigint(lits[2], None)
             if len(lits) > 2 and lits[2] is not None else -1)
        if not (0 <= m < 60 and 0 <= s < 60):
            return None
        sign = "-" if h < 0 else ""
        return f"{sign}{abs(h):02d}:{m:02d}:{s:02d}"
    if op == "date_format":
        fmt = str(lits[1]) if len(lits) > 1 else "%Y-%m-%d"
        if dtype is not None and dtype.oid in (dt.TypeOid.DATETIME,
                                               dt.TypeOid.TIMESTAMP):
            base = _dtm.datetime(1970, 1, 1) \
                + _dtm.timedelta(microseconds=int(v))
        else:
            base = _dtm.datetime(1970, 1, 1) + _dtm.timedelta(days=int(v))
        out = []
        i = 0
        while i < len(fmt):
            if fmt[i] == "%" and i + 1 < len(fmt):
                code = fmt[i:i + 2]
                i += 2
                if code == "%e":
                    out.append(str(base.day))
                elif code == "%c":
                    out.append(str(base.month))
                elif code in _MYSQL_FMT:
                    out.append(base.strftime(_MYSQL_FMT[code]))
                else:
                    out.append(code[1])
            else:
                out.append(fmt[i])
                i += 1
        return "".join(out)
    raise EvalError(op)


def _unscaled_literal(a):
    """Literal argument value with decimals unscaled to their real
    magnitude (stored scaled: 3.7 at scale 1 is the integer 37)."""
    if not isinstance(a, BoundLiteral):
        return None
    v = a.value
    if v is not None and a.dtype.oid == dt.TypeOid.DECIMAL64:
        return v / 10 ** a.dtype.scale
    return v


def _num2str_parts(e: BoundFunc, ex: ExecBatch):
    """(col, unique_vals, inverse_codes, formatted) for a numeric->string
    function — shared by eval and dictionary derivation so codes and
    dict entries always line up. Cached per (expression, batch): the
    projection asks for the dict AND the values, and the unique+format
    pass must not run twice (same motivation as uuid_dict's cache)."""
    cache = getattr(ex, "_num2str_cache", None)
    if cache is None:
        cache = {}
        ex._num2str_cache = cache
    key = id(e)
    if key in cache:
        return cache[key]
    col = eval_expr(e.args[0], ex)
    vals = np.asarray(jax.device_get(col.data))
    uniq, inv = np.unique(vals, return_inverse=True)
    lits = [None] + [_unscaled_literal(a) for a in e.args[1:]]
    strs = [_num2str_value(e.op, u, lits, e.args[0].dtype) for u in uniq]
    cache[key] = (col, uniq, inv, strs)
    return cache[key]


def num2str_final_dict(e: BoundFunc, ex: ExecBatch):
    _col, _u, _inv, strs = _num2str_parts(e, ex)
    uniq = {}
    for v in strs:
        uniq.setdefault("" if v is None else str(v), len(uniq))
    return list(uniq)


def _eval_num2str(e: BoundFunc, ex: ExecBatch) -> DeviceColumn:
    col, _u, inv, strs = _num2str_parts(e, ex)
    uniq = {}
    remap = np.empty(len(strs), np.int32)
    nulls = np.empty(len(strs), np.bool_)
    for i, v in enumerate(strs):
        remap[i] = uniq.setdefault("" if v is None else str(v), len(uniq))
        nulls[i] = v is None
    codes = jnp.asarray(remap[inv].astype(np.int32))
    validity = col.validity & ~jnp.asarray(nulls[inv])
    return DeviceColumn(codes, validity, e.dtype)


def _eval_string_func(e: BoundFunc, ex: ExecBatch) -> DeviceColumn:
    col, d, lits = _string_arg_info(e, ex)
    if col is None:
        # all-literal subject: a const code-0 column over the 1-entry dict
        col = DeviceColumn(jnp.zeros((1,), jnp.int32),
                           jnp.ones((1,), jnp.bool_), dt.VARCHAR)
    vals = [_apply_string_func(e.op, s, lits) for s in d]
    nulls = np.asarray([v is None for v in vals], dtype=np.bool_)
    codes0 = jnp.clip(col.data, 0, len(d) - 1)
    validity = col.validity
    if nulls.any():
        validity = validity & ~jnp.asarray(nulls)[codes0]
    if not e.dtype.is_varlen:
        # result type decides the LUT dtype: the binder already typed
        # the call (INT64 for length/instr/..., BOOL for regexp_like/...)
        npdt = (np.bool_ if e.dtype.oid == dt.TypeOid.BOOL
                else e.dtype.np_dtype)
        lut = np.asarray([0 if v is None else v for v in vals],
                         dtype=npdt)
        out = jnp.asarray(lut)[codes0]
        return DeviceColumn(out, validity, e.dtype)
    # varchar result: re-encode to the transformed value space so
    # GROUP BY upper(x) groups by VALUE, not by original code
    uniq = {}
    remap = np.empty(len(vals), np.int32)
    for i, v in enumerate(vals):
        remap[i] = uniq.setdefault("" if v is None else str(v), len(uniq))
    codes = jnp.asarray(remap)[codes0]
    return DeviceColumn(codes, validity, e.dtype)


def string_func_final_dict(e: BoundFunc, ex: ExecBatch):
    """Dict matching _eval_string_func's re-encoded code space."""
    out_dict = string_func_output_dict(e, ex)
    uniq = {}
    for v in out_dict:
        uniq.setdefault("" if v is None else str(v), len(uniq))
    return list(uniq)


_SIMPLE = {
    "add": S.add, "sub": S.sub, "mul": S.mul, "div": S.div, "mod": S.mod,
    "and": S.logical_and, "or": S.logical_or,
    "abs": S.abs_, "floor": S.floor, "ceil": S.ceil, "sqrt": S.sqrt,
    "exp": S.exp, "ln": S.ln, "sin": S.sin, "cos": S.cos, "power": S.power,
    "coalesce": S.coalesce,
    "tan": S.tan, "asin": S.asin, "acos": S.acos, "atan": S.atan,
    "atan2": S.atan2, "cot": S.cot, "degrees": S.degrees,
    "radians": S.radians, "log2": S.log2, "log10": S.log10,
    "sign": S.sign, "greatest": S.greatest, "least": S.least,
}

_CMP = {"eq": S.eq, "ne": S.ne, "lt": S.lt, "le": S.le, "gt": S.gt,
        "ge": S.ge}


def case_string_dict(e: BoundCase) -> List[str]:
    """Deterministic dictionary for a CASE with string-literal branches
    (ProjectOp uses the same function to attach the output dictionary)."""
    out: List[str] = []
    branches = [v for _, v in e.whens] + ([e.else_] if e.else_ else [])
    for v in branches:
        if isinstance(v, BoundLiteral) and isinstance(v.value, str):
            if v.value not in out:
                out.append(v.value)
        elif v is not None:
            raise EvalError("string CASE branches must be literals for now")
    return out or [""]


def _eval_case_strings(e: BoundCase, ex: ExecBatch) -> DeviceColumn:
    d = case_string_dict(e)
    code_of = {s: i for i, s in enumerate(d)}

    def code_col(v) -> DeviceColumn:
        if v is None or (isinstance(v, BoundLiteral) and v.value is None):
            return DeviceColumn.const_null(dt.INT32)
        return DeviceColumn.const(code_of[v.value], dt.INT32)

    out = code_col(e.else_)
    for cond, val in reversed(e.whens):
        out = S.case_when(eval_expr(cond, ex), code_col(val), out)
    # tag with the SQL string type; dict attached by the projection
    return DeviceColumn(out.data, out.validity, e.dtype)


def _eval_func(e: BoundFunc, ex: ExecBatch) -> DeviceColumn:
    op = e.op
    if op in _CMP:
        return _eval_compare(e, ex)
    if op == "not":
        return S.logical_not(eval_expr(e.args[0], ex))
    if op == "neg":
        return S.neg(eval_expr(e.args[0], ex))
    if op == "round":
        a = eval_expr(e.args[0], ex)
        digits = e.args[1].value if len(e.args) > 1 else 0
        return S.round_(a, int(digits))
    if op == "truncate":
        a = eval_expr(e.args[0], ex)
        digits = e.args[1].value if len(e.args) > 1 else 0
        return S.truncate(a, int(digits))
    if op in _DATE_FUNCS:
        return _eval_date_func(e, ex)
    if op == "time_bucket":
        from matrixone_tpu.sql.expr import BoundLiteral as _BL
        if not isinstance(e.args[1], _BL):
            raise EvalError("time_bucket width must be a literal")
        width = int(e.args[1].value)
        if width <= 0:
            raise EvalError("time_bucket width must be positive")
        a = eval_expr(e.args[0], ex)
        data = a.data.astype(jnp.int64)
        out = (data // width) * width     # floor division: window start
        return DeviceColumn(out.astype(a.data.dtype), a.validity, e.dtype)
    if op == "date_add_days":
        a = eval_expr(e.args[0], ex)
        delta = eval_expr(e.args[1], ex)
        da, db, valid = S._broadcast2(a, delta)
        return DeviceColumn((da.astype(jnp.int32) + db.astype(jnp.int32)),
                            valid, dt.DATE)
    if op in ("year", "month", "day"):
        a = eval_expr(e.args[0], ex)
        y, m, d = _civil_from_days(a.data.astype(jnp.int64))
        out = {"year": y, "month": m, "day": d}[op]
        return DeviceColumn(out.astype(jnp.int32), a.validity, dt.INT32)
    if op in ("l2_distance", "l2_distance_sq", "cosine_distance",
              "inner_product", "cosine_similarity"):
        return _eval_distance(e, ex)
    if op in _STRING_FUNCS:
        return _eval_string_func(e, ex)
    if op in _NUM2STR_FUNCS:
        return _eval_num2str(e, ex)
    if op == "date_add_unit":
        return _eval_date_add_unit(e, ex)
    if op in ("timestampadd", "timestampdiff"):
        return _eval_timestamp_fn(e, ex)
    if op in ("makedate", "period_add", "period_diff"):
        return _eval_period_fn(e, ex)
    if op == "to_datetime":
        a = eval_expr(e.args[0], ex)
        data = a.data.astype(jnp.int64)
        if a.dtype.oid == dt.TypeOid.DATE:
            data = data * _US_PER_DAY
        return DeviceColumn(data, a.validity, dt.DATETIME)
    if op == "bit_count":
        a = eval_expr(e.args[0], ex)
        x = a.data.astype(jnp.uint64)
        # Hacker's Delight popcount, 64-bit, fully vectorized
        m1 = jnp.uint64(0x5555555555555555)
        m2 = jnp.uint64(0x3333333333333333)
        m4 = jnp.uint64(0x0F0F0F0F0F0F0F0F)
        h01 = jnp.uint64(0x0101010101010101)
        x = x - ((x >> jnp.uint64(1)) & m1)
        x = (x & m2) + ((x >> jnp.uint64(2)) & m2)
        x = (x + (x >> jnp.uint64(4))) & m4
        x = (x * h01) >> jnp.uint64(56)
        return DeviceColumn(x.astype(jnp.int64), a.validity, dt.INT64)
    if op == "rand":
        n = ex.padded_len
        seed = (int(e.args[0].value) if e.args
                and isinstance(e.args[0], BoundLiteral) else None)
        rng = np.random.default_rng(seed)
        vals = jnp.asarray(rng.random(n))
        return DeviceColumn(vals, jnp.ones((n,), jnp.bool_), dt.FLOAT64)
    if op == "uuid":
        n = ex.padded_len
        codes = jnp.arange(n, dtype=jnp.int32)
        return DeviceColumn(codes, jnp.ones((n,), jnp.bool_), e.dtype)
    if op == "llm_embed":
        return _eval_llm_embed(e, ex)
    if op in _SIMPLE:
        args = [eval_expr(a, ex) for a in e.args]
        return _SIMPLE[op](*args)
    raise EvalError(f"unsupported function {op}")


def _eval_llm_embed(e: BoundFunc, ex: ExecBatch) -> DeviceColumn:
    """llm_embed(text) -> vecf32: one endpoint call per DISTINCT
    dictionary entry; embeddings gather on device by code."""
    from matrixone_tpu import llm as _llm
    from matrixone_tpu.frontend.session import current_session
    sess = current_session()
    variables = sess.variables if sess else None
    dim = e.dtype.dim
    arg = e.args[0]
    d = _dict_of(arg, ex)
    if d is None:
        if isinstance(arg, BoundLiteral) and isinstance(arg.value, str):
            vec = _llm.embed(arg.value, dim, variables)
            data = jnp.asarray([vec], jnp.float32)
            return DeviceColumn(data, jnp.ones((1,), jnp.bool_), e.dtype)
        raise EvalError("llm_embed() needs a varchar column or literal")
    col = eval_expr(arg, ex)
    mat = np.zeros((max(len(d), 1), dim), np.float32)
    for i, s in enumerate(d):
        mat[i] = _llm.embed(s, dim, variables)
    codes = jnp.clip(col.data, 0, max(len(d) - 1, 0))
    out = jnp.asarray(mat)[codes]
    return DeviceColumn(out, col.validity, e.dtype)


def uuid_dict(ex: ExecBatch):
    """uuid() dictionary: one fresh v4 uuid per row position. Cached on
    the batch so eval codes and the projection's dict agree."""
    import uuid as _uuid
    cache = getattr(ex, "_uuid_dict", None)
    if cache is None or len(cache) != ex.padded_len:
        cache = [str(_uuid.uuid4()) for _ in range(ex.padded_len)]
        try:
            object.__setattr__(ex, "_uuid_dict", cache)
        except Exception:          # noqa: BLE001 — plain attribute works
            ex._uuid_dict = cache
    return cache


def _eval_date_add_unit(e: BoundFunc, ex: ExecBatch) -> DeviceColumn:
    """date_add/date_sub with any interval unit. Calendar units go
    through civil decomposition with MySQL day clamping (Jan 31 + 1
    month = Feb 28); time units ride microseconds."""
    a = eval_expr(e.args[0], ex)
    n = int(e.args[1].value)
    unit = str(e.args[2].value)
    is_dt_in = a.dtype.oid in (dt.TypeOid.DATETIME, dt.TypeOid.TIMESTAMP)
    micros = a.data.astype(jnp.int64) * (1 if is_dt_in else _US_PER_DAY)
    if unit in ("microsecond", "second", "minute", "hour"):
        mult = {"microsecond": 1, "second": 1_000_000,
                "minute": 60_000_000, "hour": 3_600_000_000}[unit]
        out = micros + n * mult
        return DeviceColumn(out, a.validity, dt.DATETIME)
    days = jnp.floor_divide(micros, _US_PER_DAY)
    tod = micros - days * _US_PER_DAY
    if unit in ("day", "week"):
        nd = days + n * (7 if unit == "week" else 1)
    else:
        months = {"month": n, "quarter": 3 * n, "year": 12 * n}[unit]
        y, m, d = _civil_from_days(days)
        tot = y * 12 + (m - 1) + months
        ny, nm = tot // 12, tot % 12 + 1
        # clamp to the target month's length (MySQL semantics)
        mlen = _days_from_civil(ny + (nm == 12), jnp.where(nm == 12, 1,
                                                          nm + 1), 1) \
            - _days_from_civil(ny, nm, 1)
        nd2 = jnp.minimum(d, mlen)
        nd = _days_from_civil(ny, nm, nd2)
    if e.dtype.oid == dt.TypeOid.DATETIME:
        return DeviceColumn(nd * _US_PER_DAY + tod, a.validity,
                            dt.DATETIME)
    return DeviceColumn(nd.astype(jnp.int32), a.validity, dt.DATE)


def _eval_timestamp_fn(e: BoundFunc, ex: ExecBatch) -> DeviceColumn:
    unit = str(e.args[0].value).lower().rstrip("s")
    if e.op == "timestampadd":
        from matrixone_tpu.sql.expr import BoundLiteral as _BL
        n = int(e.args[1].value)
        inner = BoundFunc("date_add_unit",
                          [e.args[2], _BL(n, dt.INT64),
                           _BL(unit, dt.VARCHAR)], dt.DATETIME)
        return _eval_date_add_unit(inner, ex)
    # timestampdiff(unit, a, b) = (b - a) in unit, truncated
    a = eval_expr(e.args[1], ex)
    b = eval_expr(e.args[2], ex)
    da, db, valid = S._broadcast2(a, b)
    ua = da.astype(jnp.int64) * (1 if a.dtype.oid in
                                 (dt.TypeOid.DATETIME,
                                  dt.TypeOid.TIMESTAMP) else _US_PER_DAY)
    ub = db.astype(jnp.int64) * (1 if b.dtype.oid in
                                 (dt.TypeOid.DATETIME,
                                  dt.TypeOid.TIMESTAMP) else _US_PER_DAY)
    diff = ub - ua
    if unit in ("microsecond", "second", "minute", "hour", "day", "week"):
        div = {"microsecond": 1, "second": 1_000_000,
               "minute": 60_000_000, "hour": 3_600_000_000,
               "day": _US_PER_DAY, "week": 7 * _US_PER_DAY}[unit]
        out = jnp.sign(diff) * (jnp.abs(diff) // div)
        return DeviceColumn(out.astype(jnp.int64), valid, dt.INT64)
    days_a = jnp.floor_divide(ua, _US_PER_DAY)
    days_b = jnp.floor_divide(ub, _US_PER_DAY)
    ya, ma, dda = _civil_from_days(days_a)
    yb, mb, ddb = _civil_from_days(days_b)
    months = (yb * 12 + mb) - (ya * 12 + ma)
    # partial month does not count (MySQL truncation) — compare
    # (day-of-month, time-of-day) lexicographically, not just the day
    toa = ua - days_a * _US_PER_DAY
    tob = ub - days_b * _US_PER_DAY
    b_before_a = (ddb < dda) | ((ddb == dda) & (tob < toa))
    a_before_b = (ddb > dda) | ((ddb == dda) & (tob > toa))
    months = months - jnp.where((months > 0) & b_before_a, 1, 0) \
        + jnp.where((months < 0) & a_before_b, 1, 0)
    div = {"month": 1, "quarter": 3, "year": 12}.get(unit)
    if div is None:
        raise EvalError(f"unsupported timestampdiff unit {unit!r}")
    out = jnp.sign(months) * (jnp.abs(months) // div)
    return DeviceColumn(out.astype(jnp.int64), valid, dt.INT64)


def _eval_period_fn(e: BoundFunc, ex: ExecBatch) -> DeviceColumn:
    if e.op == "makedate":
        y = eval_expr(e.args[0], ex)
        doy = eval_expr(e.args[1], ex)
        dy, dd, valid = S._broadcast2(y, doy)
        jan1 = _days_from_civil(dy.astype(jnp.int64), jnp.int64(1),
                                jnp.int64(1))
        out = (jan1 + dd.astype(jnp.int64) - 1).astype(jnp.int32)
        valid = valid & (dd.astype(jnp.int64) >= 1)
        return DeviceColumn(out, valid, dt.DATE)
    a = eval_expr(e.args[0], ex)
    b = eval_expr(e.args[1], ex)
    da, db, valid = S._broadcast2(a, b)
    pa = da.astype(jnp.int64)
    mo_a = (pa // 100) * 12 + pa % 100 - 1

    if e.op == "period_add":
        tot = mo_a + db.astype(jnp.int64)
        out = (tot // 12) * 100 + tot % 12 + 1
        return DeviceColumn(out, valid, dt.INT64)
    pb = db.astype(jnp.int64)
    mo_b = (pb // 100) * 12 + pb % 100 - 1
    return DeviceColumn(mo_a - mo_b, valid, dt.INT64)


def _eval_compare(e: BoundFunc, ex: ExecBatch) -> DeviceColumn:
    a_raw, b_raw = e.args
    a_dict, b_dict = _dict_of(a_raw, ex), _dict_of(b_raw, ex)
    a_is_str_lit = isinstance(a_raw, BoundLiteral) and _is_varchar(a_raw.dtype)
    b_is_str_lit = isinstance(b_raw, BoundLiteral) and _is_varchar(b_raw.dtype)
    if a_is_str_lit and b_is_str_lit:
        la, lb = str(a_raw.value), str(b_raw.value)
        hit = {"eq": la == lb, "ne": la != lb, "lt": la < lb,
               "le": la <= lb, "gt": la > lb, "ge": la >= lb}[e.op]
        return DeviceColumn.const(bool(hit), dt.BOOL)
    if a_dict is not None or b_dict is not None or a_is_str_lit or b_is_str_lit:
        # string comparison: evaluate on the dictionary, gather on codes
        if a_dict is not None and (b_is_str_lit or b_dict is not None):
            col_e, other = a_raw, b_raw
            d = a_dict
            flip = False
        elif b_dict is not None and a_is_str_lit:
            col_e, other = b_raw, a_raw
            d = b_dict
            flip = True
        else:
            raise EvalError("unsupported string comparison")
        col = eval_expr(col_e, ex)
        if isinstance(other, BoundLiteral):
            lit = str(other.value)
            op = e.op
            if flip:
                op = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le"}.get(op, op)
            cmp_fn = {"eq": lambda s: s == lit, "ne": lambda s: s != lit,
                      "lt": lambda s: s < lit, "le": lambda s: s <= lit,
                      "gt": lambda s: s > lit, "ge": lambda s: s >= lit}[op]
            lut = np.array([cmp_fn(s) for s in d], dtype=np.bool_)
            hit = jnp.asarray(lut)[jnp.clip(col.data, 0, len(d) - 1)]
            return DeviceColumn(hit, col.validity, dt.BOOL)
        # column vs column over the SAME dictionary (same table column)
        other_col = eval_expr(other, ex)
        if _dict_of(other, ex) is d and e.op in ("eq", "ne"):
            return _CMP[e.op](col, other_col)
        raise EvalError("cross-dictionary string comparison not supported yet")
    return _CMP[e.op](eval_expr(a_raw, ex), eval_expr(b_raw, ex))


def _eval_distance(e: BoundFunc, ex: ExecBatch) -> DeviceColumn:
    a = eval_expr(e.args[0], ex)
    b = eval_expr(e.args[1], ex)
    da, db, valid = S._broadcast2(a, b)
    fn = {"l2_distance": D.l2_distance_rowwise,
          "l2_distance_sq": lambda x, y: D.l2_distance_rowwise(x, y) ** 2,
          "cosine_distance": D.cosine_distance_rowwise,
          "inner_product": D.inner_product_rowwise,
          "cosine_similarity": lambda x, y: 1.0 - D.cosine_distance_rowwise(x, y),
          }[e.op]
    return DeviceColumn(fn(da, db), valid, dt.FLOAT64)


def _like_regex(pattern: str) -> "re.Pattern":
    out = []
    for ch in pattern:
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
    return re.compile("^" + "".join(out) + "$", re.DOTALL)


_MONTH_NAMES = ["January", "February", "March", "April", "May", "June",
                "July", "August", "September", "October", "November",
                "December"]
_DAY_NAMES = ["Monday", "Tuesday", "Wednesday", "Thursday", "Friday",
              "Saturday", "Sunday"]

_DATE_FUNCS = {"weekofyear", "to_seconds",
               "weekday", "dayofweek", "dayofyear", "quarter", "week",
               "last_day", "to_days", "from_days", "datediff", "hour",
               "minute", "second", "date", "unix_timestamp",
               "from_unixtime", "monthname", "dayname",
               "microsecond", "yearweek"}

_US_PER_DAY = 86_400_000_000


def _days_col(col: DeviceColumn) -> jnp.ndarray:
    """Epoch days from a DATE (days) or DATETIME/TIMESTAMP (micros)."""
    if col.dtype.oid in (dt.TypeOid.DATETIME, dt.TypeOid.TIMESTAMP):
        return jnp.floor_divide(col.data.astype(jnp.int64), _US_PER_DAY)
    return col.data.astype(jnp.int64)


def _days_from_civil(y, m, d):
    """Inverse of _civil_from_days (Hinnant, public domain)."""
    y = y - (m <= 2)
    era = jnp.where(y >= 0, y, y - 399) // 400
    yoe = y - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def _eval_date_func(e: BoundFunc, ex: ExecBatch) -> DeviceColumn:
    op = e.op
    a = eval_expr(e.args[0], ex)
    if op == "datediff":
        b = eval_expr(e.args[1], ex)
        da, db, valid = S._broadcast2(a, b)
        out = (_days_col(DeviceColumn(da, valid, a.dtype))
               - _days_col(DeviceColumn(db, valid, b.dtype)))
        return DeviceColumn(out.astype(jnp.int64), valid, dt.INT64)
    if op == "from_days":
        out = a.data.astype(jnp.int64) - 719528
        return DeviceColumn(out.astype(jnp.int32), a.validity, dt.DATE)
    if op == "from_unixtime":
        out = a.data.astype(jnp.int64) * 1_000_000
        return DeviceColumn(out, a.validity, dt.DATETIME)
    if op in ("hour", "minute", "second"):
        us = a.data.astype(jnp.int64)
        sec_of_day = jnp.floor_divide(us, 1_000_000) % 86_400
        out = {"hour": sec_of_day // 3600,
               "minute": (sec_of_day // 60) % 60,
               "second": sec_of_day % 60}[op]
        return DeviceColumn(out.astype(jnp.int32), a.validity, dt.INT32)
    days = _days_col(a)
    if op == "date":
        return DeviceColumn(days.astype(jnp.int32), a.validity, dt.DATE)
    if op == "to_days":
        return DeviceColumn(days + 719528, a.validity, dt.INT64)
    if op == "to_seconds":
        # MySQL TO_SECONDS: seconds since year 0 = TO_DAYS*86400 + time
        base = (days + 719528).astype(jnp.int64) * 86_400
        if a.dtype.oid in (dt.TypeOid.DATETIME, dt.TypeOid.TIMESTAMP):
            us = a.data.astype(jnp.int64)
            base = base + (us - jnp.floor_divide(us, _US_PER_DAY)
                           * _US_PER_DAY) // 1_000_000
        return DeviceColumn(base, a.validity, dt.INT64)
    if op == "weekofyear":
        # ISO-8601 week number (MySQL week(d, 3)): the week containing
        # this date's Thursday, numbered within that Thursday's year
        th = days + 3 - (days + 3) % 7      # Monday-start week's Thursday
        ty, tm, td = _civil_from_days(th)
        jan1 = _days_from_civil(ty, jnp.ones_like(tm), jnp.ones_like(td))
        wk = (th - jan1) // 7 + 1
        return DeviceColumn(wk.astype(jnp.int32), a.validity, dt.INT32)
    if op == "unix_timestamp":
        if a.dtype.oid in (dt.TypeOid.DATETIME, dt.TypeOid.TIMESTAMP):
            out = jnp.floor_divide(a.data.astype(jnp.int64), 1_000_000)
        else:
            out = days * 86_400
        return DeviceColumn(out, a.validity, dt.INT64)
    if op == "weekday":        # 0 = Monday (1970-01-01 was a Thursday)
        return DeviceColumn(((days + 3) % 7).astype(jnp.int32),
                            a.validity, dt.INT32)
    if op == "dayofweek":      # 1 = Sunday
        return DeviceColumn(((days + 4) % 7 + 1).astype(jnp.int32),
                            a.validity, dt.INT32)
    if op == "dayname":
        return DeviceColumn(((days + 3) % 7).astype(jnp.int32),
                            a.validity, e.dtype)
    y, m, d = _civil_from_days(days)
    if op == "monthname":
        return DeviceColumn((m - 1).astype(jnp.int32), a.validity,
                            e.dtype)
    if op == "quarter":
        return DeviceColumn(((m + 2) // 3).astype(jnp.int32),
                            a.validity, dt.INT32)
    if op == "dayofyear":
        jan1 = _days_from_civil(y, jnp.ones_like(m), jnp.ones_like(d))
        return DeviceColumn((days - jan1 + 1).astype(jnp.int32),
                            a.validity, dt.INT32)
    if op == "week":           # MySQL default mode 0: Sunday-start weeks
        jan1 = _days_from_civil(y, jnp.ones_like(m), jnp.ones_like(d))
        doy = days - jan1 + 1
        jan1_dow_sun0 = (jan1 + 4) % 7
        first_sunday_doy = 1 + (7 - jan1_dow_sun0) % 7
        wk = jnp.where(doy < first_sunday_doy, 0,
                       (doy - first_sunday_doy) // 7 + 1)
        return DeviceColumn(wk.astype(jnp.int32), a.validity, dt.INT32)
    if op == "last_day":
        ny = jnp.where(m == 12, y + 1, y)
        nm = jnp.where(m == 12, 1, m + 1)
        out = _days_from_civil(ny, nm, jnp.ones_like(d)) - 1
        return DeviceColumn(out.astype(jnp.int32), a.validity, dt.DATE)
    if op == "microsecond":
        if a.dtype.oid in (dt.TypeOid.DATETIME, dt.TypeOid.TIMESTAMP):
            us = a.data.astype(jnp.int64) % 1_000_000
        else:
            us = jnp.zeros_like(a.data, jnp.int64)
        return DeviceColumn(us.astype(jnp.int32), a.validity, dt.INT32)
    if op == "yearweek":       # mode 0: YYYYWW, week-0 days belong to
        # the previous year's last week (MySQL yearweek semantics)
        jan1 = _days_from_civil(y, jnp.ones_like(m), jnp.ones_like(d))
        doy = days - jan1 + 1
        jan1_dow_sun0 = (jan1 + 4) % 7
        first_sunday_doy = 1 + (7 - jan1_dow_sun0) % 7
        wk = jnp.where(doy < first_sunday_doy, 0,
                       (doy - first_sunday_doy) // 7 + 1)
        # week 0: recompute as last week of the PREVIOUS year
        pj = _days_from_civil(y - 1, jnp.ones_like(m), jnp.ones_like(d))
        pdoy = days - pj + 1
        pdow = (pj + 4) % 7
        pfirst = 1 + (7 - pdow) % 7
        pwk = jnp.where(pdoy < pfirst, 0, (pdoy - pfirst) // 7 + 1)
        out = jnp.where(wk > 0, y * 100 + wk, (y - 1) * 100 + pwk)
        return DeviceColumn(out.astype(jnp.int64), a.validity, dt.INT64)
    raise EvalError(op)


def _civil_from_days(z: jnp.ndarray):
    """Epoch days -> (year, month, day); Howard Hinnant's civil algorithm
    (public domain), integer-only so it runs on device."""
    z = z + 719468
    era = jnp.where(z >= 0, z, z - 146096) // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    y = jnp.where(m <= 2, y + 1, y)
    return y, m, d
