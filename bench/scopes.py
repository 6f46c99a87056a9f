"""Device time of a traced run by the engine's name scopes.

The program names its device work with ``jax.named_scope``: ``engine/``
then ``setup``, ``push/wire``, ``push/check``, ``hub``, ``pull/wire``,
``pull/rank``, ``pull/search`` or ``fold``. A scope lives in the
``op_name`` metadata of each instruction of the compiled program; a
fusion carries its root's. The trace's device events name an
instruction by its HLO text, so each event is matched to its instruction
in the compiled text of the programs the run ran: by its name, result
type and operands' names, or, where the event shows no operands, by
``trace.short_name``. Programs of different plans number their
instructions alike, so a key that carries different scopes in different
programs, or none, counts as unscoped.

Times are self times (``trace.self_times``): a loop's event does not
count its body again."""
from __future__ import annotations

import re

from bench import trace

SCOPES = ("setup", "push/wire", "push/check", "hub", "pull/wire",
          "pull/rank", "pull/search", "fold")
_SCOPE = re.compile(r"engine/(" + "|".join(SCOPES) + r")(?![A-Za-z_])")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTR = re.compile(r"^\s*(?:ROOT )?(%[^ ]+ = .*)$")
_OPERAND = re.compile(r"%[\w.\-]+")


def _closing(s: str, depth: int = 0) -> int:
    """Index just past the parenthesis that closes the ``depth`` already
    open at the start of ``s`` (the end of ``s`` if none does)."""
    for i, ch in enumerate(s):
        depth += (ch == "(") - (ch == ")")
        if depth == 0 and ch == ")":
            return i + 1
    return len(s)


def instr_key(instr: str) -> tuple:
    """``(short_name, operand names)`` of one HLO instruction's text, as
    the compiled program prints it or as a trace event names it."""
    rest = instr.partition(" = ")[2]
    # skip the result type: a tuple in parentheses, or one word
    rest = rest[_closing(rest):] if rest.startswith("(") \
        else rest.partition(" ")[2]
    args = rest.partition("(")[2]
    return (trace.short_name(instr),
            tuple(_OPERAND.findall(args[:_closing(args, 1)])))


def scope_of(op_name: str) -> str | None:
    """The innermost engine scope named in an ``op_name``, without the
    ``engine/`` root; None where there is none."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


def hlo_scopes(texts) -> dict:
    """Scope of every instruction in the compiled HLO ``texts``, under
    its ``instr_key`` and under its ``short_name``; None for a key with
    no engine scope, or with different scopes in different texts."""
    out: dict = {}
    for text in texts:
        for line in text.splitlines():
            m = _INSTR.match(line)
            if m is None:
                continue
            op = _OP_NAME.search(line)
            sc = scope_of(op.group(1)) if op else None
            for key in (instr_key(m.group(1)), trace.short_name(m.group(1))):
                out[key] = sc if out.get(key, sc) == sc else None
    return out


def event_scope(scopes: dict, name: str) -> str | None:
    """The scope of a device event named ``name`` (its HLO text)."""
    key = instr_key(name)
    if key[1] and key in scopes:
        return scopes[key]
    return scopes.get(key[0])


def programs(traffic) -> list[str]:
    """Compiled HLO text of each program a one-shot traffic ran: each of
    its graphs planned and sharded as its surveys were, and the program
    it compiled for that plan lowered again (the compile cache answers)."""
    from bench import surveys
    from repro.core.dodgr import shard_dodgr
    from repro.core.pushpull import plan_engine

    lay, texts = traffic.layout, []
    for hg in traffic.hgs:
        bundle = surveys.make(traffic.names, traffic.params)
        cfg, _ = plan_engine(hg, lay["S"], bundle, mode=lay["mode"],
                             orient=lay["orient"])
        fn = traffic.fns.get(cfg)
        if fn is None:
            continue
        gr, _ = shard_dodgr(hg, lay["S"], hub_theta=cfg.hub_theta,
                            orient=lay["orient"])
        texts.append(fn.lower(gr).compile().as_text())
    return texts


def by_scope(run) -> dict | None:
    """Device seconds of the traced window by engine scope, summed over
    chips, with the rest under ``"unscoped"``; None where the trace saw
    no device or the programs carry no engine scope."""
    if not run.trace.devices:
        return None
    scopes = hlo_scopes(programs(run.traffic))
    if not any(scopes.values()):
        return None
    out = dict.fromkeys(SCOPES + ("unscoped",), 0.0)
    for ops in run.trace.devices.values():
        for name, ns in trace.self_times(ops).items():
            sc = event_scope(scopes, name) or "unscoped"
            out[sc] += ns * 1e-9
    return out


def ms_per_survey(run, *scopes: str) -> float | None:
    """Device milliseconds per survey under the given scopes."""
    secs, n = by_scope(run), len(run.traffic.answers)
    if secs is None or not n:
        return None
    return 1e3 * sum(secs[s] for s in scopes) / n
