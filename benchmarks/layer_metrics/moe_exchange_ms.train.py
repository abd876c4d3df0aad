"""Device time per step of the experts' exchange: every operation under
an expert layer's `exchange_out` and `exchange_back` scopes and the
compiler's fused reduce-scatters of the way back, which keep no scope
(`harness/trace_chips.py:exchange_events`): the collectives' own
operations and what waits on them (an asynchronous collective's `-done`
is the wait), all phases, the chips' mean. Nothing to read in a program
without those scopes."""
import statistics

from harness import trace_chips


def read(run):
    times = [trace_chips.exchange_ms(chip) for chip in trace_chips.of(run)]
    times = [t for t in times if t is not None]
    return statistics.mean(times) if times else None
