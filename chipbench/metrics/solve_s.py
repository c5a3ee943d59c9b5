"""Wall seconds of the window over the solves completed in it (host
clock).  The window ends when the solve running at --seconds completes."""


def read(run):
    answers = run.window.answers
    if not answers:
        return None
    return run.window.seconds / len(answers)
