"""Milliseconds a proposal spent between the solver's last result and
the list of proposals: span ``analyzer.proposal_diff`` (the stats program,
the fetch of both assignments, the O(P) comparison)."""
from benchlib.spans import read_spans as read  # noqa: F401
