"""Set-up seconds: from process start to the window's start, compiling,
making the data and warming every shape included (host clock)."""


def read(reading):
    return reading.setup_s
