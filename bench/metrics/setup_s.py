"""Wall seconds from process start to the first measured question:
imports, building the surface, and the set-up question with every compile
or persistent-cache read it makes."""


def read(run):
    return run.setup_s
