"""Drafts the main model accepted over drafts the MTP module offered, over
the steps that landed in the window (counts): ``accepted`` / ``drafted`` on
the program's ``serve.engine.step`` rows. A step yields ``1 + this`` tokens a
slot. With seeded weights under the configuration's temperature 1.0 it is
what speculative sampling accepts between two unrelated distributions
(~48 % by count); a trained model's MTP head reads 85-90 %."""

from perfbench import gigachat_bytes as gb


def read(ctx):
    rows = gb.draft_steps(ctx)
    drafted = sum(f["drafted"] for f in rows)
    return 100.0 * sum(f["accepted"] for f in rows) / drafted \
        if drafted else None
