"""Operations that answered inside the window with rows equal to the
reference's, per second of the window."""


def read(ctx):
    return ctx["completed_correct_in_window"] / ctx["window_s"]
