"""Connectivity epoch: mean host time of the first ``label()`` after each
mutation, the call that pays ``_ensure_comp``'s rebuild."""


def read(run):
    first = run.label_after_mutation
    return 1e3 * sum(first) / len(first) if first else None
