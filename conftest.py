"""Keep pytest out of the unpacked checkouts and scratch scripts that
parent-vs-change chip runs leave in the repo (listed in .gitignore):
their test modules share names with the repo's own and would shadow them."""
collect_ignore = ["_checkouts", "_work", "chiprun_out"]
