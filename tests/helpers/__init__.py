# Makes in-test imports like ``from helpers.serving_oracle import ...``
# resolve (pytest prepends tests/ to sys.path).  dist_check.py and
# sharded_check.py stay standalone subprocess scripts.
