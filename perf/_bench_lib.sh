# Shared helpers for the perf shell runbook (sourced by sweep.sh).
# Requires $OUT to be set by the sourcing script. Every emitted line is valid
# JSON; a command that dies leaves an explicit {"section":"error",...} record
# carrying the tail of its stderr (diagnosable, not just 'failed/hung').

note() {
    python -c "import json,sys;print(json.dumps({'section':'cmd','argv':sys.argv[1]}))" "$*" | tee -a "$OUT"
}

err_record() {  # $1=argv  $2=stderr-file
    python - "$1" "$2" <<'PY' | tee -a "$OUT"
import json, sys
tail = ""
try:
    with open(sys.argv[2], errors="replace") as f:
        tail = " | ".join(l.strip() for l in f.readlines()[-3:] if l.strip())[:500]
except OSError:
    pass
print(json.dumps({"section": "error", "argv": sys.argv[1],
                  "error": "command failed, hung (watchdog), or produced no output",
                  "stderr_tail": tail}))
PY
}

# watchdog for one command. The commands run one after another, never two at
# once: a chip has one owner at a time.
WATCHDOG_S=3600

# run CMD...: emit cmd record, run under the watchdog, record the LAST stdout
# line (bench.py's JSON) or an error record with stderr tail
run() {
    note "$*"
    local line etmp
    etmp=$(mktemp)
    if line=$(timeout "$WATCHDOG_S" "$@" 2>"$etmp" | tail -1) && [ -n "$line" ]; then
        echo "$line" | tee -a "$OUT"
    else
        err_record "$*" "$etmp"
    fi
    rm -f "$etmp"
}

# run_all CMD...: same, but records EVERY stdout line (multi-record sections)
run_all() {
    note "$*"
    local out etmp
    etmp=$(mktemp)
    if out=$(timeout "$WATCHDOG_S" "$@" 2>"$etmp") && [ -n "$out" ]; then
        echo "$out" | tee -a "$OUT"
    else
        err_record "$*" "$etmp"
    fi
    rm -f "$etmp"
}
