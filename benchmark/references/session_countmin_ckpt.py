"""``session_countmin``'s comparison (loaded, not copied: every
session exact, every estimate within Count-Min's bounds) and, for the
deployment that checkpoints, two more parts that ``checkpointing``
holds the books for:

- **the checkpoint ledger** (``checkpointing.ledger``): every
  checkpoint triggered inside the measured window completed, at least
  floor(window_s / interval) - 1 of them, consecutive completions lie
  at most 10 s (two intervals) apart (the recovery point; over the
  completions inside the window, from the last one before it), none
  failed or aborted, each committed the consumer's offsets to the log
  once, and at the end of the window the log's committed offsets were
  the newest completed checkpoint's;
- **the recovery** (``checkpointing.recover``), after the timed
  window, in the same process: the timed job's device state is
  released, a fresh environment runs the same job over a plain bounded
  consumer of the same log from the checkpoint the storage retains
  (``set_savepoint_restore`` on the checkpoint directory), whichever
  that is, to the end of the log; its rows must be the timed run's
  rows for the sessions that fired after the checkpoint, integer for
  integer, with no row dropped as late and at least one session open
  across the checkpoint.  How much of the log it replayed
  (``recovery_replayed_periods``) is a fact of the run: the last
  checkpoint may fall into the closing period.  A snapshot that misses
  a row of state, or offsets that do not belong to the state, give
  other totals: every row that differs is a failure.

``attempted`` is ``session_countmin``'s; a problem of the ledger or of
the recovery makes the run not correct whatever the count.
"""

import checkpointing
import loader

_plain = loader.load_module("references", "session_countmin")
sessions_of = _plain.sessions_of


def check(config, emitted, results):
    verdict = _plain.check(config, emitted, results)
    window_s = None
    t0, end = (checkpointing.noted(m, "clock_ms") for m in ("t0", "end"))
    if t0 is not None and end is not None:
        window_s = (end - t0) / 1e3
    facts, problems = checkpointing.ledger(config, window_s or 0.0)
    r_facts, r_problems = checkpointing.recover(config, results)
    verdict["facts"] = {**verdict["facts"], **facts, **r_facts}
    verdict["problems"] = (verdict["problems"] + problems
                           + r_problems)[:20]
    return verdict
