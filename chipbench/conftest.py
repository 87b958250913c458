import sys
from pathlib import Path

# the benchmark imports the program from the checkout's src/
_SRC = str(Path(__file__).resolve().parent.parent / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
