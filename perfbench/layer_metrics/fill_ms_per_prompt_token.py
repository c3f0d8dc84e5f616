"""The closed loop's fill (first request sent until every slot decodes, the
part of ``setup_s`` that is admissions) over the prompt tokens of the
requests whose first token came before the window opened."""


def read(ctx):
    run = ctx["run"]
    if not run.get("fill_s"):
        return None
    tokens = sum(s.req.prompt_len for s in run["sent"]
                 if s.times and s.times[0] <= run["t_open"])
    return run["fill_s"] * 1e3 / tokens if tokens else None
