"""Positive fixture for rule ``format`` under PEP 701 tokenizing: the
quote nested in the f-string's replacement field is legal, the standalone
single-quoted key is not — exactly one finding."""

KEY = 'label'


def describe(entry):
    return f"retired:{entry['label']}"
