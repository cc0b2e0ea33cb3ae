#!/usr/bin/env python3
"""The benchmark's self-check: python3 benchmark/selfcheck.py [pytest args]."""
import os
import sys

import pytest

if __name__ == "__main__":
    here = os.path.dirname(os.path.abspath(__file__))
    sys.exit(pytest.main([os.path.join(here, "tests"), "-q", "-p",
                          "no:cacheprovider", *sys.argv[1:]]))
