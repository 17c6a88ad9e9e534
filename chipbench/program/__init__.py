"""The only files of the benchmark that import the program under test:
one builder per model family, found by the ``program`` key of a
configuration's file."""
