"""Process start to the window's first step."""


def read(c):
    return c['setup_s']
