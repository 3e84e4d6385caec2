"""What decides ``correct``: the plain reference (``reference``) and the
comparison and its numbers (``compare``)."""
