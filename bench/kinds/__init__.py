"""The traffic loops, one module per ``kind`` a traffic file can name:
``kinds/<kind>.py`` defines ``Loop`` (see ``loops.py``)."""
