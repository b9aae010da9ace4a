"""Shared HLO-text inspection helpers.

Every hot-path pin in this repo ultimately asserts something about the
optimized HLO that XLA compiled for a jitted function: that the sharded
chunk step contains no collectives, that observability folds stay on the
device (no outfeeds or host callbacks), that donated buffers actually
alias, that the pre-transposed weight mirrors are not re-transposed at
run time.  Before this module existed each test grew its own ad-hoc
string grep; the scanners here are the single source of truth so the
contract checker (``repro.analysis.contracts``) and the test suite agree
byte-for-byte on what counts as a violation.

All helpers operate on the *optimized* HLO text, i.e. the string
returned by ``jitted.lower(*args).compile().as_text()``.  Ops that XLA
fuses are still visible inside fusion bodies, so the op histogram counts
them too.
"""
from __future__ import annotations

import re
from collections import Counter
from typing import Iterable, List, Sequence, Tuple

# Tokens that indicate cross-device communication.  Matches the pin
# introduced for the sharded serving path (PR 5): GSPMD regressions show
# up as one of these op names in the optimized module.
COLLECTIVE_TOKENS: Tuple[str, ...] = (
    "all-reduce",
    "all-gather",
    "collective-permute",
    "all-to-all",
    "reduce-scatter",
)

# Tokens that indicate a device->host (or host->device) transfer inside
# the compiled step.  Matches the observability pin (PR 6): telemetry
# must fold on device and only cross the boundary at chunk edges.
HOST_TRANSFER_TOKENS: Tuple[str, ...] = (
    "outfeed",
    "infeed",
    "xla_python_cpu_callback",      # jax < 0.5 custom-call target
    "xla_ffi_python_cpu_callback",  # the FFI target jax 0.9 emits
    "host_callback",
    "SendToHost",
    "RecvFromHost",
)

# Optimized HLO instruction lines look like
#   ``%name = f32[4,32]{1,0} op-name(%a, %b), ...`` or
#   ``ROOT %name = (f32[...]) op-name(...)``.
# The op name is the token immediately before the open paren after the
# shape.  This matches instructions inside fusion computations too.
_OP_RE = re.compile(
    r"=\s+(?:\([^)]*\)|[a-z0-9]+\[[^\]]*\])(?:\{[^}]*\})?\**\s+"
    r"([a-z][a-z0-9\-]*(?:\.[0-9]+)?)\("
)

# ``input_output_alias={ {0}: (0, {}, may-alias), {1}: (1, {}, may-alias) }``
# on the HloModule header line records which outputs alias which inputs —
# the compile-time footprint of ``donate_argnums``.  The body nests
# braces, so it is extracted by brace counting, not regex.
_ALIAS_KEY = "input_output_alias={"
_ALIAS_ENTRY_RE = re.compile(r"\{[0-9, ]*\}:\s*\(")


def matching_lines(hlo_text: str, tokens: Sequence[str]) -> List[str]:
    """Lines of ``hlo_text`` containing any of ``tokens`` (substring match)."""
    return [
        line
        for line in hlo_text.splitlines()
        if any(tok in line for tok in tokens)
    ]


def collective_lines(hlo_text: str) -> List[str]:
    """HLO lines mentioning a cross-device collective."""
    return matching_lines(hlo_text, COLLECTIVE_TOKENS)


def count_collectives(hlo_text: str) -> int:
    """Number of HLO lines mentioning a collective (the PR-5 pin)."""
    return len(collective_lines(hlo_text))


# Where in the Python source each op was traced: ``metadata={op_name=...
# stack_frame_id=N}`` on an instruction, plus the module's source tables
# (one header line each, entries up to the next blank line).  Two programs
# traced from different call sites differ only in these.
_METADATA_RE = re.compile(r",?\s*metadata=\{[^{}]*\}")
_SOURCE_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                  "StackFrames")


def strip_metadata(hlo_text: str) -> str:
    """``hlo_text`` without source-location metadata."""
    out, in_table = [], False
    for line in hlo_text.splitlines():
        if line in _SOURCE_TABLES:
            in_table = True
        elif in_table and not line.strip():
            in_table = False
        elif not in_table:
            out.append(_METADATA_RE.sub("", line))
    return "\n".join(out)


def host_transfer_lines(hlo_text: str) -> List[str]:
    """HLO lines mentioning a host transfer or host callback (the PR-6 pin)."""
    return matching_lines(hlo_text, HOST_TRANSFER_TOKENS)


def op_histogram(hlo_text: str) -> Counter:
    """Histogram of op names across the module, including fusion bodies.

    Versioned op names (``fusion.1``) are folded onto their base name.
    """
    counts: Counter = Counter()
    for m in _OP_RE.finditer(hlo_text):
        name = m.group(1).split(".")[0]
        counts[name] += 1
    return counts


def count_ops(hlo_text: str, op: str) -> int:
    """Occurrences of one op family (base name, fusion bodies included)."""
    return op_histogram(hlo_text).get(op, 0)


def dtype_violation_lines(hlo_text: str, max_dtype: str = "float32") -> List[str]:
    """Lines whose result dtype exceeds ``max_dtype``.

    Only the f32 ceiling is meaningful for this repo (weights, states and
    logits are all float32; int32 bookkeeping is always allowed).  A wider
    ceiling disables the check.
    """
    if max_dtype in ("float64", "f64", None):
        return []
    # x64 leaks show up as f64 compute or s64 index math on the hot path.
    return matching_lines(hlo_text, ("f64[", "c128["))


def alias_count(hlo_text: str) -> int:
    """Number of input/output alias entries on the HloModule header.

    Each entry corresponds to one donated leaf that XLA agreed to reuse
    for an output buffer.  Donation that silently failed (shape/dtype
    mismatch, or a leaf not reachable from an output) simply has no
    entry, so comparing this count against the number of donated leaves
    catches dropped donations at compile time.  Note the *aliased-input*
    runtime failure (one buffer bound to two donated params) is not
    visible here — the runtime probe in ``contracts.check_case`` covers it.
    """
    i = hlo_text.find(_ALIAS_KEY)
    if i < 0:
        return 0
    j = i + len(_ALIAS_KEY)
    depth = 1
    while j < len(hlo_text) and depth:
        if hlo_text[j] == "{":
            depth += 1
        elif hlo_text[j] == "}":
            depth -= 1
        j += 1
    return len(_ALIAS_ENTRY_RE.findall(hlo_text[i + len(_ALIAS_KEY):j - 1]))


def compiled_text(jitted, *args, **kwargs) -> str:
    """Optimized HLO for ``jitted`` lowered at ``args``/``kwargs``."""
    return jitted.lower(*args, **kwargs).compile().as_text()


def assert_no_tokens(hlo_text: str, tokens: Iterable[str], what: str) -> None:
    """Raise AssertionError with offending lines if any token appears."""
    hits = matching_lines(hlo_text, tuple(tokens))
    if hits:
        raise AssertionError(
            f"{what}: found {len(hits)} offending HLO line(s):\n"
            + "\n".join(hits[:8])
        )
