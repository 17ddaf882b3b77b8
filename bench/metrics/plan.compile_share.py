"""Share of the whole passes' wall time that ``Session.run`` spent in its
compile stage (``ResultSet.stage_ns['compile']``: prepares, which read the
compile cache; pipelined passes overlap part of it with timing), in
percent."""


def read(run):
    pass_s = run.data.get("pass_s")
    if not pass_s:
        return None
    return 100.0 * run.data["stage_ns"]["compile"] * 1e-9 / sum(pass_s)
