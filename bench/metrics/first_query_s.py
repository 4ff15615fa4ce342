"""Seconds of the first ``submit().result()`` on the registered data set:
planning, packing and uploading the query class, and compiling it."""


def read(run):
    return run.first_query_s
