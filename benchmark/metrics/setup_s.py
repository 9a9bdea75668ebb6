"""Process start to the first timed request: city and traffic load, server or
child start, table build, warm-up (compiles included)."""


def read(rec):
    return rec["setup_s"]
