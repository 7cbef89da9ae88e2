"""Requests answered inside the window over the window's seconds; a kNN
request counts once, at its final unflagged answer."""


def read(run):
    return run.answered_in_window() / run.seconds
