"""Share, in percent, of the program's spans named `span` whose attrs
meet `where` (`{"chunk": ["gt", 1]}`) among all spans of that name that
start inside the traced stretch."""
from chipbench import program_spans


def read(result, span, outer, where):
    found = program_spans.in_stretch(result, outer)
    if not found:
        return None
    named = [s for s in found[0] if s["name"] == span]
    if not named:
        return None
    return 100.0 * sum(program_spans.meets(s, where)
                       for s in named) / len(named)
