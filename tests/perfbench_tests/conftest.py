"""``fit/`` holds what a later PR would bring, test files among it: they are
run where ``test_fit.py`` puts them, beside a copy of the harness, and are
not collected in place."""

collect_ignore = ["fit"]
