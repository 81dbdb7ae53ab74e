"""Sequitur grammar induction (Nevill-Manning & Witten 1997).

Needed by the RRA baseline (Senin et al. 2015): RRA discretizes the
series with SAX, induces a context-free grammar with Sequitur, and uses
*rule coverage density* as the rarity signal guiding the discord search.

Classic linked-list implementation with a digram index and the two
Sequitur invariants (digram uniqueness, rule utility).  The load-bearing
correctness property — expanding the grammar reproduces the input token
stream exactly — is property-tested in tests/test_rra.py.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple


class _Symbol:
    __slots__ = ("value", "rule", "prev", "next", "owner")

    def __init__(self, value: Optional[int] = None,
                 rule: "Optional[_Rule]" = None):
        self.value = value          # terminal token (int) or None
        self.rule = rule            # _Rule for non-terminals, else None
        self.prev: Optional[_Symbol] = None
        self.next: Optional[_Symbol] = None
        self.owner: Optional[_Rule] = None   # set on guards only

    @property
    def is_guard(self) -> bool:
        return self.owner is not None

    def key(self):
        return ("R", self.rule.id) if self.rule is not None \
            else ("T", self.value)


class _Rule:
    __slots__ = ("id", "guard", "refcount")

    def __init__(self, rid: int):
        self.id = rid
        self.guard = _Symbol()
        self.guard.owner = self
        self.guard.prev = self.guard
        self.guard.next = self.guard
        self.refcount = 0

    def symbols(self) -> List[_Symbol]:
        out, s = [], self.guard.next
        while s is not self.guard:
            out.append(s)
            s = s.next
        return out


class Grammar:
    def __init__(self):
        self._next_id = 0
        self.start = self._new_rule()
        self.digrams: Dict[Tuple, _Symbol] = {}

    # -- plumbing -------------------------------------------------------
    def _new_rule(self) -> _Rule:
        r = _Rule(self._next_id)
        self._next_id += 1
        return r

    @staticmethod
    def _insert_after(left: _Symbol, sym: _Symbol) -> None:
        sym.prev = left
        sym.next = left.next
        left.next.prev = sym
        left.next = sym

    @staticmethod
    def _remove(sym: _Symbol) -> None:
        sym.prev.next = sym.next
        sym.next.prev = sym.prev

    @staticmethod
    def _digram_key(a: _Symbol) -> Optional[Tuple]:
        if a.is_guard or a.next is None or a.next.is_guard:
            return None
        return (a.key(), a.next.key())

    def _forget_digram(self, a: _Symbol) -> None:
        k = self._digram_key(a)
        if k is not None and self.digrams.get(k) is a:
            del self.digrams[k]

    @staticmethod
    def _owner_rule(sym: _Symbol) -> "_Rule":
        s = sym
        while not s.is_guard:
            s = s.prev
        return s.owner

    # -- public construction ---------------------------------------------
    def append_token(self, tok: int) -> None:
        last = self.start.guard.prev
        sym = _Symbol(value=int(tok))
        self._insert_after(last, sym)
        if not last.is_guard:
            self._check_digram(last)

    # -- invariants --------------------------------------------------------
    def _check_digram(self, a: _Symbol) -> None:
        k = self._digram_key(a)
        if k is None:
            return
        match = self.digrams.get(k)
        if match is None:
            self.digrams[k] = a
            return
        if match is a or match.next is a or a.next is match:
            return                                    # same / overlapping
        # Case 1: the matched digram is the complete RHS of a rule → reuse.
        if match.prev.is_guard and match.next.next is match.prev:
            rule = self._owner_rule(match)
            if rule is not self.start:
                self._substitute(a, rule)
                self._enforce_utility(rule)
                return
        # Case 2: make a new rule from the digram.
        rule = self._new_rule()
        pa = _Symbol(value=match.value, rule=match.rule)
        pb = _Symbol(value=match.next.value, rule=match.next.rule)
        if pa.rule is not None:
            pa.rule.refcount += 1
        if pb.rule is not None:
            pb.rule.refcount += 1
        self._insert_after(rule.guard, pa)
        self._insert_after(pa, pb)
        self.digrams[k] = pa
        self._substitute(match, rule)
        self._substitute(a, rule)
        self._enforce_utility(rule)

    def _enforce_utility(self, rule: "_Rule") -> None:
        """Inline any sub-rule of `rule` now referenced fewer than twice."""
        for s in rule.symbols():
            if s.rule is not None and s.rule.refcount < 2:
                self._expand(s)

    def _substitute(self, a: _Symbol, rule: "_Rule") -> None:
        """Replace digram (a, a.next) with a non-terminal for `rule`."""
        b = a.next
        self._forget_digram(a.prev)
        self._forget_digram(a)
        self._forget_digram(b)
        nt = _Symbol(rule=rule)
        rule.refcount += 1
        if a.rule is not None:
            a.rule.refcount -= 1
        if b.rule is not None:
            b.rule.refcount -= 1
        left = a.prev
        self._remove(a)
        self._remove(b)
        self._insert_after(left, nt)
        # an overlapping run (xxx) indexes one of its two digrams; if
        # that one went, the survivor beside the edit takes its place
        for s in (left.prev, nt.next):
            k = self._digram_key(s)
            if k is not None:
                self.digrams.setdefault(k, s)
        if not left.is_guard:
            self._check_digram(left)
        if not nt.next.is_guard and self._digram_key(nt) is not None:
            self._check_digram(nt)

    def _expand(self, nt: _Symbol) -> None:
        """Rule utility: inline a rule referenced only once."""
        rule = nt.rule
        left = nt.prev
        self._forget_digram(left)
        self._forget_digram(nt)
        # drop the rule's own digram index entries
        for s in rule.symbols():
            self._forget_digram(s)
        self._remove(nt)
        prev = left
        for s in rule.symbols():
            c = _Symbol(value=s.value, rule=s.rule)
            self._insert_after(prev, c)
            if prev is not left:
                # the rule's own digrams move with its symbols: index
                # them at their new place, or a later repeat is missed
                self.digrams[self._digram_key(prev)] = prev
            prev = c
        if not left.is_guard:
            self._check_digram(left)
        tail = prev
        if not tail.is_guard and tail.next is not None \
                and not tail.next.is_guard:
            self._check_digram(tail)

    # -- outputs ------------------------------------------------------------
    def _index_rules(self) -> Dict[int, _Rule]:
        by_id: Dict[int, _Rule] = {}

        def walk(rule: _Rule):
            if rule.id in by_id:
                return
            by_id[rule.id] = rule
            for s in rule.symbols():
                if s.rule is not None:
                    walk(s.rule)
        walk(self.start)
        return by_id

    def expand_tokens(self) -> List[int]:
        """Terminal stream of the start rule (must equal the input)."""
        out: List[int] = []

        def walk(rule: _Rule):
            for s in rule.symbols():
                if s.rule is None:
                    out.append(s.value)
                else:
                    walk(s.rule)
        walk(self.start)
        return out

    def n_rules(self) -> int:
        return len(self._index_rules())

    def terminal_spans(self) -> List[Tuple[int, int, int]]:
        """(first_terminal_idx, last_terminal_idx, depth) per non-terminal
        occurrence reachable from the start rule."""
        lengths: Dict[int, int] = {}

        def length_of(rule: _Rule) -> int:
            if rule.id in lengths:
                return lengths[rule.id]
            tot = 0
            for s in rule.symbols():
                tot += 1 if s.rule is None else length_of(s.rule)
            lengths[rule.id] = tot
            return tot

        spans: List[Tuple[int, int, int]] = []

        def walk(rule: _Rule, start_idx: int, depth: int):
            idx = start_idx
            for s in rule.symbols():
                if s.rule is None:
                    idx += 1
                else:
                    ln = length_of(s.rule)
                    spans.append((idx, idx + ln - 1, depth))
                    walk(s.rule, idx, depth + 1)
                    idx += ln
        walk(self.start, 0, 0)
        return spans


def sequitur(tokens) -> Grammar:
    g = Grammar()
    for t in tokens:
        g.append_token(int(t))
    return g
