"""Seconds per completed job: the window, from its start to the end of its
last job, over the jobs that returned an answer."""


def read(run):
    done = len(run.completed)
    return run.window_s / done if done else None
