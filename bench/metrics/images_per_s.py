"""Images whose answers came back in the window, over the window's
seconds (host clock)."""
UNIT = "images/s"


def read(run):
    return len(run.window.done_in_window()) / run.seconds
