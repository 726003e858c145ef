"""Rule ``format``: the executable slice of the ruff-format gate.

History: since PR 3 the CI workflow has declared ``ruff format --check``
over an ever-widening tree, but ruff cannot install in the build container,
so every PR verified the gate "best-effort" with hand-rolled approximations
— the declared-vs-executed gap ROADMAP's standing CI item admits.  This
tokenize-based probe EXECUTES the mechanically-checkable portion of that
gate everywhere Python runs, scoped to exactly the trees the workflow's
``ruff format --check`` step claims (``src/repro/core``,
``src/repro/kernels``, ``src/repro/models``, ``benchmarks/``):

* line length <= 88 (``pyproject.toml`` ``line-length``) — stricter than
  the formatter itself, which leaves long comments/strings alone, so the
  ruff-format gate could pass a line this probe flags; the repo's
  convention is 88 for those too, and the pragma escape exists for the
  rare unsplittable literal;
* double quotes for string literals (``quote-style = "double"``), except
  strings whose body contains a double quote — ruff keeps single quotes
  there to avoid escaping — and strings nested in an f-string's
  replacement fields (``f"x:{d['k']}"``), which Python 3.12 (PEP 701)
  tokenizes as STRING tokens between FSTRING_START and FSTRING_END;
* no trailing whitespace.
"""

from __future__ import annotations

import tokenize

from .. import registry

_MAX_LEN = 88
_PREFIX_CHARS = "rbfuRBFU"
# PEP 701 f-string token types (Python >= 3.12); older tokenizers emit a
# whole f-string as one STRING token and never produce these
_FSTRING_START = getattr(tokenize, "FSTRING_START", None)
_FSTRING_END = getattr(tokenize, "FSTRING_END", None)


@registry.rule(
    "format",
    scope=(
        "src/repro/core/*.py",
        "src/repro/kernels/*.py",
        "src/repro/kernels/*/*.py",
        "src/repro/models/*.py",
        "benchmarks/*.py",
    ),
    description="executed format gate for the ruff-format-claimed trees: "
    "<=88-char lines, double quotes, no trailing whitespace",
)
def check(ctx, project):
    for i, line in enumerate(ctx.lines, start=1):
        if len(line) > _MAX_LEN:
            yield ctx.finding(
                "format",
                i,
                f"line is {len(line)} chars (> {_MAX_LEN}); wrap it "
                f"(ruff line-length)",
                col=_MAX_LEN,
            )
        if line != line.rstrip():
            yield ctx.finding(
                "format",
                i,
                "trailing whitespace",
                col=len(line.rstrip()),
            )
    fstring_depth = 0
    for tok in ctx.tokens:
        if tok.type == _FSTRING_START:
            fstring_depth += 1
        elif tok.type == _FSTRING_END:
            fstring_depth -= 1
        if tok.type != tokenize.STRING or fstring_depth:
            continue
        body = tok.string.lstrip(_PREFIX_CHARS)
        if body.startswith("'"):
            quote = "'''" if body.startswith("'''") else "'"
            inner = body[len(quote) : -len(quote)]
            if '"' not in inner:
                yield ctx.finding(
                    "format",
                    tok.start[0],
                    "single-quoted string; the format gate's quote-style "
                    'is "double"',
                    col=tok.start[1],
                )
