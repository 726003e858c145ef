"""Negative fixture for rule ``format`` under PEP 701 tokenizing: Python
3.12 tokenizes the nested ``'k'`` as its own STRING token, which the rule
must not read as a single-quoted literal."""


def describe(d):
    return f"x:{d['k']}"
