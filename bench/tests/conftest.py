import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (HERE, os.path.dirname(HERE),
          os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
