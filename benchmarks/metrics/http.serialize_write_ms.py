"""Milliseconds a proposal spent turning the body into JSON text and
writing it to the socket: the program's spans ``http.serialize`` and
``http.write`` of the endpoint's requests."""
from benchlib.spans import read_spans as read  # noqa: F401
