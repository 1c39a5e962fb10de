"""`python -m repro.obs report <trace.jsonl>` renders telemetry — see
report.py."""
import sys

from .report import main

sys.exit(main())
