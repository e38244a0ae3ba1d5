"""The client's hook runner: hooks run in time order between ticks, and a
hook can schedule the next one, as the trace's start schedules its stop."""
import asyncio

from bench.client import Clock, _watch


def test_a_hook_schedules_the_next():
    clock = Clock()
    seen = []

    def later():
        seen.append(("later", clock.now()))

    def first():
        seen.append(("first", clock.now()))
        return (clock.now() + 0.05, later)

    asyncio.run(_watch(clock, 1.0, [(0.02, first),
                                    (0.03, lambda: seen.append(("mid", 0)))]))
    assert [n for n, _ in seen] == ["first", "mid", "later"]
    assert seen[2][1] - seen[0][1] >= 0.05
