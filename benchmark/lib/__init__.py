"""The benchmark's own code: the yardstick. Nothing here imports the program
except ``server_proc`` (the child that builds and serves the deployment)."""
