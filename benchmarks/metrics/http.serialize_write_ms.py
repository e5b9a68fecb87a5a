"""Milliseconds a proposal spent turning the body into JSON text and
writing it to the socket: the program's spans ``http.serialize`` and
``http.write`` of the requests of the cell's operation."""
from benchlib.spans import read_endpoint_spans as read  # noqa: F401
