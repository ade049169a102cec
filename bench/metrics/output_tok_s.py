"""Output tokens produced inside the window over the window's length
(host clock): all the work and all the time of the window."""


def read(run):
    return run.window_output_tokens() / run.seconds
