"""Where a compiled program's HLO text holds its sorts (not a test file:
``test_decode_plane.py`` reads the CPU's text with it,
``test_decode_pool_v5e_compile.py`` the text of a described v5e)."""
import re

_HEAD = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_SORT = re.compile(r'\s(sort|topk)\(|custom_call_target="TopK"')
_ATTR = re.compile(r"(\w+)=(\{[^}]*\}|%?[\w.\-]+)")
_NAME = re.compile(r"[\w.\-]+")
_BRANCH_KEYS = ("branch_computations", "true_computation",
                "false_computation")


def sorts_outside_a_branch(text):
    """``(outside, inside)``: the names of the computations holding a sort
    or top-k op that the entry computation reaches WITHOUT entering a
    ``conditional``'s branch — work every launch pays for — and of those
    it reaches only through one."""
    bodies, entry, name = {}, None, None
    for line in text.splitlines():
        head = _HEAD.match(line)
        if head:
            name = head.group(2)
            bodies[name] = []
            if head.group(1):
                entry = name
        elif name is not None:
            bodies[name].append(line)
    assert entry is not None, "no ENTRY computation in the HLO text"

    def callees(lines, through_branches):
        out = set()
        for line in lines:
            for key, value in _ATTR.findall(line):
                if key in _BRANCH_KEYS and not through_branches:
                    continue
                out.update(n for n in _NAME.findall(value) if n in bodies)
        return out

    def reach(through_branches):
        seen, todo = set(), [entry]
        while todo:
            n = todo.pop()
            if n not in seen:
                seen.add(n)
                todo.extend(callees(bodies[n], through_branches))
        return seen

    holders = {n for n, lines in bodies.items()
               if any(_SORT.search(line) for line in lines)}
    outside = holders & reach(False)
    return outside, (holders & reach(True)) - outside
