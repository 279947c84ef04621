"""The benchmark's harness: everything the yardstick is made of.

Nothing here imports the program (``paddle_tpu``) except ``serve.py``
and ``train.py`` (the system under test) and the ``families`` modules'
``program_config`` (the mapping from published keys to the program's
config object).
"""
