"""Fleet rounds: mean merged rounds a request rides in (``Request.rounds``)."""


def read(run):
    done = run.window.answered()
    return sum(s.req.rounds for s in done) / len(done) if done else None
