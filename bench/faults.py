"""Faults planted in the program under test, to see a run's check come out
not correct: ``plant(case)`` breaks the program in place and returns what
undoes it.

- ``no_exchange``: a migrated slot's cache rows are not sent to the other
  chip (its token and position rows still are);
- ``altered``: each decoded token replaced by the next id, where it is made.
"""
from __future__ import annotations

CASES = ("no_exchange", "altered")


def plant(case: str):
    import repro.serve.batcher as batcher
    from repro.core.program import Resident

    if case not in CASES:
        raise ValueError(f"unknown fault {case!r}; known: {list(CASES)}")
    saved = (batcher.BatchGroup._copy_slot_state, batcher.make_decode_step)

    def undo():
        batcher.BatchGroup._copy_slot_state, batcher.make_decode_step = saved

    if case == "no_exchange":
        def copy(self, slot, dst, d):
            for src_buf, dst_buf in zip(self._row_bufs(), dst._row_bufs()):
                if not isinstance(src_buf, Resident):
                    dst_buf[d] = src_buf[slot]
                    dst._patch_or_invalidate(dst_buf, [d])
            return True
        batcher.BatchGroup._copy_slot_state = copy
    else:
        make = batcher.make_decode_step

        def factory(cfg, api):
            step = make(cfg, api)

            def broken(*args):
                tok, cache = step(*args)
                return (tok + 1) % cfg.vocab, cache
            return broken
        batcher.make_decode_step = factory
    return undo
