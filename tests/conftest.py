# Tests run on the single real CPU device. The 512-device forcing is ONLY
# for launch/dryrun.py (own process) — never set it here.
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
