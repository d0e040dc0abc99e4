"""Share of the traced window in which the device is idle and no program
span is open on the host, %. Also prints the idle seconds by innermost
open program span, and the spread of the clock offset, to stderr."""
import json
import sys

from chipbench import program_spans


def read(run):
    got = program_spans.idle_by_program_span(run)
    if got is None:
        return None
    idle, spread_ns = got
    print(f"idle_by_program_span {json.dumps(idle)} clock_spread_us "
          f"{spread_ns / 1e3!r}", file=sys.stderr)
    return (idle.get(program_spans.UNATTRIBUTED, 0.0) / run.trace.window_s
            * 100)
