"""Plain references, and each configuration's reference check.

A configuration's file names three things of its own by dotted path under
``program``: ``reference`` (the plain forward pass), ``reference_weights``
(the program's parameter tree in the reference's convention) and, for a
served configuration, ``reference_check``. The harness knows none of them by
name: a family with another state, prefill or routing brings its own files.

**The contract of a ``reference_check``** (``perfbench/replica.py`` calls it,
``perfbench/runners/serve.py`` judges what it returns):

    check(engine, prompt, emitted, config, shape) -> dict

It runs in the replica, where the weights and the chip are, after warm-up and
before the window, with the engine idle and its lock held. It is given

- ``engine``: the replica's engine object as the program built it;
- ``prompt``: the seeded request's ``REF_PROMPT`` token ids, ``emitted``:
  the ``REF_NEW`` tokens the engine streamed for it through its own timed
  programs (greedy);
- ``config``: the configuration file as a dict (``config["program"]`` holds
  the dotted paths), ``shape``: ``program.shape_of`` of it, as run.

It returns ``{"ok": bool, "finite": bool, "readings": [...]}`` and may add
``"notes"`` (a dict of numbers printed beside the readings, compared with
nothing). Each reading is ``{"name", "value", "limit"}``: a number compared
and the most it may be, in the same unit. The runner prints every reading
against its limit, and ``correct`` needs ``ok``, ``finite``, every ``value``
finite and at most its ``limit``, and every requested token emitted: a check
cannot pass a reading that is over its own limit.

What a check owes: it compares what the ENGINE's own programs produced with
the plain reference's full forward pass (logits, not sampled tokens: with
seeded weights the largest logit changes on rounding); each limit is written
in the check's file with the reason for it and the readings it was set from;
and the WEIGHTS one precision step under the configuration's (int8 where it
states bfloat16) must fail at least one reading, which a control beside the
check shows on the chip and a test under ``tests/perfbench/`` keeps at a toy
width. That is as far as a check is held today. The CACHE one step down is
not covered: the one request checked decodes ``REF_NEW`` tokens over
``REF_PROMPT`` cached positions, the engine hands out tokens and no decode
logits, and ``dense_check`` passes int8 pages on every reading (its file and
PERF.md section 7 give the readings). A check that sees the cache needs the
decode step's logits or a sample of the window's finished requests; until
one is written, no check is claimed to fail a lower-precision cache. What
only one family has (a private prefill entry, a routing tie rule, a
recurrent state) is imported in the check's file, with the reason, and
nowhere else under ``perfbench/``.
"""

#: the one request every check is given: a prompt in the 256 bucket, and the
#: tokens decoded for it through the cache
REF_PROMPT, REF_NEW = 200, 24
